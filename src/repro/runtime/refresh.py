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
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.core import overload as ovl, utility as util
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


def refit_latency_model(carry: eng.Carry) -> ovl.LatencyModel:
    """Refit f: n_pm -> l_p from the carry's rolling latency ring
    (``overload.fit_latency_ring``: a wrapped int32 pointer means a full
    ring)."""
    return ovl.fit_latency_ring(carry.lat_samples_n, carry.lat_samples_l,
                                carry.lat_ptr)


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
        ready = _gate(*jax.device_get((carry.obs_counts, carry.obs_rewards)),
                      rcfg, state)
    if not ready:
        return model, carry, False
    with telemetry.span("rt.refresh.fit"):
        # The deployed bin width, so the EngineModel pytree structure (and
        # the compiled chunk executable) never changes mid-stream.
        fit = runner.fit_model(specs, cfg, carry, bin_size=rcfg.bin_size,
                               width=model.ut_tables.shape[1],
                               use_remaining_time=rcfg.use_remaining_time)
        fresh, tables_finite, f_finite = jax.device_get(
            (fit.T, fit.tables_finite, fit.f_finite))
        new_f = _decide(fresh, tables_finite, f_finite, rcfg, state)
    if new_f is None:
        return model, carry, False
    with telemetry.span("rt.refresh.deploy"):
        model = model._replace(
            ut_tables=fit.ut_tables, ut_bins=fit.ut_bins,
            f_model=fit.f_model if new_f else model.f_model)
        if rcfg.decay < 1.0:
            carry = carry._replace(obs_counts=carry.obs_counts * rcfg.decay,
                                   obs_rewards=carry.obs_rewards * rcfg.decay)
    state.last_T = fresh
    state.refresh_count += 1
    return model, carry, True


def refresh_lanes(specs: Sequence[pat.PatternSpec], cfg: eng.EngineConfig,
                  model: eng.EngineModel, carry: eng.Carry,
                  rcfg: RefreshConfig, states: Sequence[RefreshState],
                  telemetry: TM.TelemetryLog, mesh=None,
                  ) -> tuple[eng.EngineModel, eng.Carry, int]:
    """``refresh_model`` on every lane of lane-stacked ``model`` and
    ``carry`` at once; returns ``(model, carry, lanes refit)``.

    Each lane is gated, fitted and deployed as ``refresh_model`` would do
    it alone (``states[l]`` is lane ``l``'s), but the lanes share each
    step: one device→host read of every lane's observations for the gates
    (``rt.refresh.obs``), one compiled fit of all lanes and one read-back
    of their chains and finiteness flags for the host's decisions
    (``rt.refresh.fit``), and one compiled deploy that takes the fit where
    a lane's decision says so and keeps the deployed model elsewhere
    (``rt.refresh.deploy``).  On ``mesh`` both programs are placed as
    ``dist.sharding.lane_refresh_programs`` places them: each device fits
    its own lanes, and model and carry keep the chunk step's sharding.
    Nothing is donated.  Counters: ``lanes_refit`` and ``lanes_skipped``.
    """
    L = len(states)
    refit = np.zeros(L, bool)
    new_f = np.zeros(L, bool)
    with telemetry.span("rt.refresh.obs"):
        obs_c, obs_r = jax.device_get((carry.obs_counts, carry.obs_rewards))
        ready = [_gate(obs_c[l], obs_r[l], rcfg, st)
                 for l, st in enumerate(states)]
    if any(ready):
        fit_p, deploy_p = _lane_programs(
            runner.query_fits(specs, cfg, rcfg.bin_size), rcfg.bin_size,
            model.ut_tables.shape[-2], rcfg.use_remaining_time, rcfg.decay,
            cfg, mesh, L)
        with telemetry.span("rt.refresh.fit"):
            fit = fit_p(carry.obs_counts, carry.obs_rewards,
                        carry.lat_samples_n, carry.lat_samples_l,
                        carry.lat_ptr)
            fresh, tables_finite, f_finite = jax.device_get(
                (fit.T, fit.tables_finite, fit.f_finite))
            for l in np.flatnonzero(ready):
                d = _decide(fresh[l], tables_finite[l], f_finite[l], rcfg,
                            states[l])
                refit[l], new_f[l] = d is not None, bool(d)
    if refit.any():
        with telemetry.span("rt.refresh.deploy"):
            model, obs = deploy_p(
                model, (carry.obs_counts, carry.obs_rewards),
                (fit.ut_tables, fit.ut_bins, fit.f_model), refit, new_f)
            if rcfg.decay < 1.0:
                carry = carry._replace(obs_counts=obs[0], obs_rewards=obs[1])
        for l in np.flatnonzero(refit):
            states[l].last_T = fresh[l]
            states[l].refresh_count += 1
    n = int(refit.sum())
    telemetry.count("lanes_refit", n)
    telemetry.count("lanes_skipped", L - n)
    return model, carry, n


@functools.lru_cache(maxsize=16)
def _lane_programs(queries: tuple, bin_size: int, width: int,
                   use_remaining_time: bool, decay: float,
                   cfg: eng.EngineConfig, mesh, num_lanes: int):
    """The lane refresh's fit and deploy, each one program over the lanes,
    built once per deployment."""
    fit = functools.partial(util.fit_model, queries=queries,
                            bin_size=bin_size, width=width,
                            use_remaining_time=use_remaining_time)

    def deploy(model, obs, new, refit, new_f):
        ut, bins, f = new
        model = model._replace(
            ut_tables=jnp.where(refit, ut, model.ut_tables),
            ut_bins=jnp.where(refit, bins, model.ut_bins),
            f_model=jax.tree.map(lambda a, b: jnp.where(new_f, a, b), f,
                                 model.f_model))
        return model, tuple(jnp.where(refit, x * decay, x) for x in obs)

    if mesh is None:
        return jax.jit(jax.vmap(fit)), jax.jit(jax.vmap(deploy))
    from repro.dist import sharding as SH
    return SH.lane_refresh_programs(fit, deploy, mesh, cfg, num_lanes)


def _gate(obs_c: np.ndarray, obs_r: np.ndarray, rcfg: RefreshConfig,
          state: RefreshState) -> bool:
    """The observations, read on the host: False where they are
    non-finite or too few, and the refresh is skipped."""
    # NaN-safe gate (DESIGN.md §12): a poisoned accumulator must SKIP the
    # refresh, not deploy corrupt tables.  Note `nan < threshold` is False
    # — the min-observation gate alone would wave NaNs straight through.
    if not (np.isfinite(obs_c).all() and np.isfinite(obs_r).all()):
        state.skipped_nonfinite += 1
        return False
    if float(obs_c.sum()) < rcfg.min_observations:
        state.skipped_obs += 1
        return False
    return True


def _decide(fresh: np.ndarray, tables_finite, f_finite, rcfg: RefreshConfig,
            state: RefreshState) -> bool | None:
    """The host's decision on a fit (``utility.fit_model``: one compiled
    program over all queries) from its chains and two finiteness flags:
    None where the drift test or non-finite tables skip the refresh, else
    whether the refitted latency model ``f`` is deployed with the
    tables."""
    if rcfg.drift_threshold > 0 and state.last_T is not None:
        mse = float(((state.last_T - fresh) ** 2).mean(axis=(1, 2)).max())
        if mse <= rcfg.drift_threshold:
            state.skipped_drift += 1
            return None
    # Same NaN discipline for the freshly built tables and the latency
    # refit: a non-finite product (e.g. an Inf-polluted latency ring that
    # degenerates the regression) keeps the deployed model.
    if not tables_finite:
        state.skipped_nonfinite += 1
        return None
    if not rcfg.refit_latency:
        return False
    if not f_finite:
        state.skipped_nonfinite += 1
    return bool(f_finite)
