"""Experiment runner: the full pSPICE lifecycle (paper §IV experimental
methodology).

  1. WARM-UP at a sustainable rate with statistic gathering on — the model
     builder's observation phase (§III-C).
  2. MODEL BUILD: transition matrices, reward matrices, MRP value iteration,
     utility tables; latency regressions f (from gathered samples) and g.
  3. MAX-THROUGHPUT measurement from the calibrated cost model at the warm
     steady-state PM population ("maximum operator throughput" in §IV-A).
  4. OVERLOAD RUN at rate = multiplier × max throughput with the chosen
     shedder, vs. a no-shed GROUND-TRUTH run on the identical stream.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.core import overload as ovl, utility as util
from repro.data import streams

Array = jnp.ndarray


@dataclasses.dataclass
class BuiltModel:
    """Everything the model builder produces."""
    ut_stacked: Array
    ut_bins: Array
    f_model: ovl.LatencyModel
    g_model: ovl.LatencyModel
    max_rate: float  # measured max operator throughput (events/s)
    steady_n_pm: float


def default_config(cp: pat.CompiledPatterns, **kw) -> eng.EngineConfig:
    """Engine config with the static pattern census filled in.

    ``backend`` selects the hot-path implementation (DESIGN.md §8/§10):
    the jnp reference scan, the per-event Pallas kernels, or the
    event-block megakernel (``backend="pallas_block"`` with
    ``block_events=W`` fused per launch) — all bitwise-equivalent, so
    experiments may pick purely on speed.  Unknown backends / bad block
    sizes fail at config-build time (``EngineConfig.__post_init__``),
    never as a silent xla-path fallback mid-experiment.
    """
    kind, sm = np.asarray(cp.kind), np.asarray(cp.spawn_mode)
    base = dict(
        num_patterns=cp.num_patterns,
        max_states=cp.max_states,
        max_classes=cp.trans.shape[2] - 1,
        max_pms=2048,
        max_any_ids=max(8, int(cp.final_state.max()) + 1),
        ring_size=8,
        # Static pattern census: lets the engine skip the per-event ops of
        # pattern families that cannot occur (bitwise-identical to "mixed").
        kinds=("seq" if (kind == pat.KIND_SEQ).all()
               else "any" if (kind == pat.KIND_ANY).all() else "mixed"),
        spawn_modes=("at_open" if (sm == pat.SPAWN_AT_OPEN).all()
                     else "in_windows" if (sm == pat.SPAWN_IN_WINDOWS).all()
                     else "mixed"),
    )
    base.update(kw)
    return eng.EngineConfig(**base)


def query_fits(specs: Sequence[pat.PatternSpec], cfg: eng.EngineConfig,
               bin_size: int) -> tuple[util.QueryFit, ...]:
    """The static per-query arguments of ``utility.fit_model``."""
    return tuple(util.QueryFit(
        num_states=s.num_states,
        num_bins=max(1, -(-s.window_size // bin_size)),
        weight=float(s.weight),
        default_reward=cfg.c_match * float(s.proc_cost)) for s in specs)


def fit_model(specs: Sequence[pat.PatternSpec], cfg: eng.EngineConfig,
              carry: eng.Carry, *, bin_size: int, width: int | None = None,
              use_remaining_time: bool = True) -> util.ModelFit:
    """``utility.fit_model`` of the queries ``specs`` from the observations
    and latency ring ``carry`` has gathered, ``width`` bins wide (default:
    the widest query's): one compiled program per query set and shapes,
    shared by the model build and every refresh."""
    queries = query_fits(specs, cfg, bin_size)
    if width is None:
        width = max(q.num_bins for q in queries)
    return util.fit_model(carry.obs_counts, carry.obs_rewards,
                          carry.lat_samples_n, carry.lat_samples_l,
                          carry.lat_ptr, queries=queries, bin_size=bin_size,
                          width=width, use_remaining_time=use_remaining_time)


def build_model(specs: Sequence[pat.PatternSpec], cfg: eng.EngineConfig,
                warm_events: streams.EventBatch, bin_size: int = 64,
                use_remaining_time: bool = True,
                seed: int = 0) -> BuiltModel:
    """Phase 1+2: warm-up run with stats on, then build everything."""
    cp = pat.compile_patterns(specs)
    # No match emission during warm-up: nothing reads the identity
    # columns here, and they would be (n_warm, P, N) of dead output.
    warm_cfg = dataclasses.replace(cfg, gather_stats=True,
                                   shedder=eng.SHED_NONE,
                                   emit_matches=False)
    model0 = eng.make_model(cp, warm_cfg)
    carry = eng.init_carry(warm_cfg, seed=seed)
    carry, outs = eng.run_engine(warm_cfg, model0, warm_events, carry)

    # Chains, utility tables and the latency regression f from the
    # gathered observations and (n_pm, t_proc) samples.
    fit = fit_model(specs, cfg, carry, bin_size=bin_size,
                    use_remaining_time=use_remaining_time)
    f_model = fit.f_model
    # g (shed latency) from the simulator's true sort-cost model — in a real
    # deployment these samples come from observed shed calls; the warm run
    # never sheds, so we use the calibrated constants directly.
    g_model = ovl.LatencyModel(a=jnp.float32(cfg.c_shed_pm),
                               b=jnp.float32(cfg.c_shed_base),
                               kind=jnp.int32(ovl.LINEAR))

    # Max throughput at the warm steady state: 1 / E[t_proc].
    n_tail = max(1, warm_events.ev_class.shape[0] // 2)
    steady_n_pm = float(np.asarray(outs.n_pm)[-n_tail:].mean())
    t_proc = float(ovl.predict_latency(f_model, jnp.float32(steady_n_pm)))
    max_rate = 1.0 / max(t_proc, 1e-9)
    return BuiltModel(ut_stacked=fit.ut_tables,
                      ut_bins=fit.ut_bins, f_model=f_model, g_model=g_model,
                      max_rate=max_rate, steady_n_pm=steady_n_pm)


def run_with_shedder(specs: Sequence[pat.PatternSpec],
                     cfg: eng.EngineConfig, built: BuiltModel,
                     raw: streams.RawStream, rate: float, shedder: str,
                     seed: int = 0,
                     pattern_parallel: bool = False) -> eng.RunResult:
    cp = pat.compile_patterns(specs)
    run_cfg = dataclasses.replace(cfg, gather_stats=False, shedder=shedder)
    events = streams.classify(specs, raw, rate=rate, seed=seed)
    model = eng.make_model(cp, run_cfg, ut_tables=built.ut_stacked,
                           ut_bins=built.ut_bins, f_model=built.f_model,
                           g_model=built.g_model,
                           ebl_raw_mean=float(
                               np.asarray(events.ebl_raw).mean()))
    carry = eng.init_carry(run_cfg, seed=seed)
    if pattern_parallel:
        # Pattern-parallel scale-out: shard the (P, N) PM store over the
        # local device mesh (repro.dist.sharding.pm_specs / shard_map).
        from repro.dist import sharding as SH
        carry, outs = SH.run_engine_sharded(run_cfg, model, events, carry)
    else:
        carry, outs = eng.run_engine(run_cfg, model, events, carry)
    return eng.summarize(carry, outs)


@dataclasses.dataclass
class ExperimentResult:
    shedder: str
    fn: float                 # weighted false-negative fraction (count-based)
    match_probability: float  # ground-truth match probability
    max_rate: float
    result: eng.RunResult
    ground_truth: eng.RunResult
    latency_bound: float = 1.0  # the configured LB the run was held to
    # Match-SET quality metrics (repro.eval.quality, DESIGN.md §9) —
    # populated when the runs emitted matches (``emit_matches``, the
    # run_experiment default).  ``fn`` above compares completion COUNTS;
    # ``fn_match`` compares identities, so a shedder that loses one match
    # while a different one completes cannot cancel the loss out.
    recall: float | None = None        # weighted |found ∩ gt| / |gt|
    fn_match: float | None = None      # 1 - recall
    per_pattern_fn: np.ndarray | None = None   # (P,)
    n_gt_matches: int = 0
    n_found_matches: int = 0

    @property
    def lb_violations(self) -> float:
        """Fraction of events whose latency exceeded the configured bound.
        An empty run (zero events) violated nothing — the unguarded
        ``mean()`` of an empty array would be NaN."""
        l_e = np.asarray(self.result.l_e)
        if l_e.size == 0:
            return 0.0
        return float((l_e > self.latency_bound).mean())

    @property
    def lb_compliance(self) -> float:
        """Fraction of events whose latency met the configured bound
        (delegates to the one §IV-B metric definition in repro.eval)."""
        from repro.eval import quality as Q
        return Q.latency_compliance(self.result.l_e, self.latency_bound)


def run_experiment(specs: Sequence[pat.PatternSpec], raw: streams.RawStream,
                   shedders: Sequence[str] = (eng.SHED_PSPICE, eng.SHED_PMBL,
                                              eng.SHED_EBL),
                   rate_multiplier: float = 1.2,
                   warm_frac: float = 0.3, latency_bound: float = 1.0,
                   bin_size: int = 64, max_pms: int = 2048,
                   use_remaining_time: bool = True,
                   seed: int = 0, pattern_parallel: bool = False,
                   emit_matches: bool = True,
                   **cfg_kw) -> dict[str, ExperimentResult]:
    """The full paper methodology on one stream; returns per-shedder results.

    With ``emit_matches`` (the default) every run emits its match
    identities and the summary carries match-SET quality metrics (recall
    / fn_match vs the no-shed ground truth) next to the legacy
    count-based ``fn``."""
    cp = pat.compile_patterns(specs)
    cfg = default_config(cp, latency_bound=latency_bound, max_pms=max_pms,
                         emit_matches=emit_matches, **cfg_kw)

    n_warm = int(raw.n * warm_frac)
    raw_warm = dataclasses.replace(
        raw, n=n_warm, type_id=raw.type_id[:n_warm], attr=raw.attr[:n_warm],
        group=raw.group[:n_warm])
    raw_run = dataclasses.replace(
        raw, n=raw.n - n_warm, type_id=raw.type_id[n_warm:],
        attr=raw.attr[n_warm:], group=raw.group[n_warm:])

    # Warm-up below capacity: use a conservative low rate.
    warm_events = streams.classify(specs, raw_warm, rate=1.0, seed=seed)
    built = build_model(specs, cfg, warm_events, bin_size=bin_size,
                        use_remaining_time=use_remaining_time, seed=seed)

    rate = built.max_rate * rate_multiplier
    gt = run_with_shedder(specs, cfg, built, raw_run, rate=rate,
                          shedder=eng.SHED_NONE, seed=seed,
                          pattern_parallel=pattern_parallel)
    weights = np.array([s.weight for s in specs])
    out = {}
    for sh in shedders:
        res = run_with_shedder(specs, cfg, built, raw_run, rate=rate,
                               shedder=sh, seed=seed,
                               pattern_parallel=pattern_parallel)
        er = ExperimentResult(
            shedder=sh,
            fn=res.false_negatives(gt, weights),
            match_probability=float(
                gt.complex_count.sum() / max(gt.pms_created.sum(), 1.0)),
            max_rate=built.max_rate,
            result=res, ground_truth=gt,
            latency_bound=latency_bound)
        if res.matches is not None and gt.matches is not None:
            # Imported here: repro.eval's public surface pulls in the
            # sweep driver, which imports this module.
            from repro.eval import quality as Q
            rep = Q.compare_match_sets(res.matches, gt.matches, weights)
            er.recall = rep.recall
            er.fn_match = rep.fn_ratio
            er.per_pattern_fn = rep.per_pattern_fn
            er.n_gt_matches = rep.n_gt
            er.n_found_matches = rep.n_found
        out[sh] = er
    return out
