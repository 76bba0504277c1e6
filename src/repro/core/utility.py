"""Utility tables for partial matches (paper §III-B, §III-C-3).

U_pm = w_q · P_pm / tau_pm  (Eq. 1), with P and tau min-max scaled to a common
range first (§III-C-3: "we bring the completion probabilities and processing
times to the same scale").  Materialized as UT_q[(ws/bs) × m] so the load
shedder does O(1) lookups (paper: "Getting the utility of a PM from UT has
only O(1) time complexity").
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.tracing import count_traces
from repro.core import markov, overload as ovl

Array = jax.Array

_EPS = 1e-6


def _minmax_scale(x: Array, lo: float = _EPS, hi: float = 1.0) -> Array:
    """Scale x into [lo, hi].  Degenerate (constant) tables map to hi."""
    return _scale_between(x, x.min(), x.max(), lo, hi)


def _minmax_scale_each(x: Array, where: Array, lo: float = _EPS,
                       hi: float = 1.0) -> Array:
    """``_minmax_scale`` of each (P, ...) query over its ``where`` entries."""
    axes = tuple(range(1, x.ndim))
    return _scale_between(
        x, x.min(axes, keepdims=True, where=where, initial=jnp.inf),
        x.max(axes, keepdims=True, where=where, initial=-jnp.inf), lo, hi)


def _scale_between(x: Array, xmin: Array, xmax: Array, lo: float,
                   hi: float) -> Array:
    span = xmax - xmin
    scaled = jnp.where(span > 0, (x - xmin) / jnp.maximum(span, 1e-30), 1.0)
    return lo + scaled * (hi - lo)


@dataclasses.dataclass
class UtilityTable:
    """Per-pattern utility table UT_q plus the tables it was derived from.

    table[j, i] = utility of a PM of this pattern in state s_i with
    (j+1)·bin_size events remaining in its window.  Index j = ceil(R_w/bs)-1;
    intermediate R_w values use linear interpolation (§III-C-1).
    """
    table: Array        # (num_bins, m)
    completion: Array   # (num_bins, m)   raw P
    remaining: Array    # (num_bins, m)   raw tau
    bin_size: int
    weight: float

    @property
    def num_bins(self) -> int:
        return self.table.shape[0]

    @property
    def num_states(self) -> int:
        return self.table.shape[1]


jax.tree_util.register_pytree_node(
    UtilityTable,
    lambda ut: ((ut.table, ut.completion, ut.remaining),
                (ut.bin_size, ut.weight)),
    lambda aux, ch: UtilityTable(*ch, bin_size=aux[0], weight=aux[1]),
)


def build_utility_table(T: Array, R: Array, window_size: int, bin_size: int,
                        weight: float = 1.0,
                        use_remaining_time: bool = True) -> UtilityTable:
    """Build UT_q from a learned transition matrix + reward matrix.

    use_remaining_time=False gives the paper's pSPICE-- ablation (denominator
    of Eq. 1 fixed to 1).
    """
    num_bins = max(1, -(-window_size // bin_size))  # ceil
    P = markov.completion_probability_table(T, num_bins, bin_size)
    tau = markov.remaining_time_table(T, R, num_bins, bin_size)
    P_s = _minmax_scale(P)
    tau_s = _minmax_scale(tau) if use_remaining_time else jnp.ones_like(tau)
    table = weight * P_s / jnp.maximum(tau_s, _EPS)
    return UtilityTable(table=table, completion=P, remaining=tau,
                        bin_size=bin_size, weight=weight)


class QueryFit(NamedTuple):
    """What ``fit_model`` needs of one query, hashable, so that a
    deployment's query set compiles the fit once."""
    num_states: int
    num_bins: int          # ceil(window_size / bin_size), at least 1
    weight: float
    default_reward: float  # R of a transition never observed


class ModelFit(NamedTuple):
    ut_tables: Array          # (P, width, M), 0 beyond each query's bins/states
    ut_bins: Array            # (P,) int32 bin sizes
    T: Array                  # (P, M, M) chains, 0 beyond each query's states
    f_model: ovl.LatencyModel  # f refitted from the latency ring
    tables_finite: Array      # bool: every entry of ut_tables is finite
    f_finite: Array           # bool: f's coefficients are finite


@partial(jax.jit, static_argnames=("queries", "bin_size", "width",
                                   "use_remaining_time"))
@count_traces("utility.fit_model")
def fit_model(obs_counts: Array, obs_rewards: Array, lat_n: Array,
              lat_l: Array, lat_ptr: Array, *,
              queries: tuple[QueryFit, ...], bin_size: int, width: int,
              use_remaining_time: bool = True) -> ModelFit:
    """The model builder (paper §III-C/§III-E) for every query at once.

    From the stacked (P, M, M) transition counts and reward sums and the
    ``(n_pm, latency)`` ring: chains, binned completion probabilities and
    remaining times out to the widest query's bins, each query's tables
    min-max scaled over its own bins and states as ``build_utility_table``
    scales them, stacked and zero-padded as ``stack_tables`` does, then
    padded or cut to ``width`` bins — plus the latency regression f.  One
    program, dispatched once.
    """
    P, M, _ = obs_counts.shape
    m = np.array([q.num_states for q in queries])
    nb = np.array([q.num_bins for q in queries])
    B = int(nb.max())
    T, R = markov.estimate_chains(obs_counts, obs_rewards, m,
                                  [q.default_reward for q in queries])
    P_tab = markov.completion_probability_tables(T, m, B, bin_size)
    tau = markov.remaining_time_tables(T, R, m, B, bin_size)
    valid = ((np.arange(B)[None, :, None] < nb[:, None, None])
             & (np.arange(M)[None, None, :] < m[:, None, None]))
    P_s = _minmax_scale_each(P_tab, valid)
    tau_s = (_minmax_scale_each(tau, valid) if use_remaining_time
             else jnp.ones_like(tau))
    w = np.array([q.weight for q in queries], np.float32)[:, None, None]
    table = jnp.where(valid, w * P_s / jnp.maximum(tau_s, _EPS), 0.0)
    table = (jnp.pad(table, ((0, 0), (0, width - B), (0, 0)))
             if width >= B else table[:, :width])
    f = ovl.fit_latency_ring(lat_n, lat_l, lat_ptr)
    padding = np.arange(M) >= m[:, None]                      # (P, M)
    return ModelFit(
        ut_tables=table, ut_bins=jnp.full((P,), bin_size, jnp.int32),
        T=jnp.where(padding[:, :, None] | padding[:, None, :], 0.0, T),
        f_model=f,
        tables_finite=jnp.isfinite(table).all(),
        f_finite=jnp.isfinite(f.a) & jnp.isfinite(f.b))


def lookup_utility(ut_table: Array, bin_size: int, state: Array,
                   r_w: Array) -> Array:
    """Vectorized O(1) utility lookup with linear interpolation between bins.

    state: (n,) int32 current states; r_w: (n,) int32/float events remaining.
    Returns (n,) float32 utilities.  R_w in [(j-1)·bs, j·bs] interpolates
    between bins j-1 and j (paper §III-C-1).
    """
    num_bins = ut_table.shape[0]
    pos = jnp.clip(r_w.astype(jnp.float32) / bin_size - 1.0, 0.0,
                   num_bins - 1.0)
    j0 = jnp.floor(pos).astype(jnp.int32)
    j1 = jnp.minimum(j0 + 1, num_bins - 1)
    frac = pos - j0.astype(jnp.float32)
    u0 = ut_table[j0, state]
    u1 = ut_table[j1, state]
    return u0 * (1.0 - frac) + u1 * frac


def stack_tables(tables: Sequence[UtilityTable],
                 max_states: int | None = None) -> tuple[Array, Array]:
    """Stack per-pattern tables into one (n_patterns, num_bins, max_m) array
    (padded with -inf so padded states are never preferred for KEEPING — they
    can't occur) + bin sizes.  Lets a multi-query operator look up utilities
    for PMs of any pattern with one gather.
    """
    if max_states is None:
        max_states = max(t.num_states for t in tables)
    num_bins = max(t.num_bins for t in tables)
    out = []
    for t in tables:
        tab = t.table
        tab = jnp.pad(tab, ((0, num_bins - t.num_bins),
                            (0, max_states - t.num_states)),
                      constant_values=0.0)
        out.append(tab)
    bins = jnp.array([t.bin_size for t in tables], jnp.int32)
    return jnp.stack(out), bins


def multi_pattern_lookup(stacked: Array, bin_sizes: Array, pattern_id: Array,
                         state: Array, r_w: Array) -> Array:
    """Utility lookup across patterns: stacked (P, B, M), all args (n,)."""
    num_bins = stacked.shape[1]
    bs = bin_sizes[pattern_id].astype(jnp.float32)
    pos = jnp.clip(r_w.astype(jnp.float32) / bs - 1.0, 0.0, num_bins - 1.0)
    j0 = jnp.floor(pos).astype(jnp.int32)
    j1 = jnp.minimum(j0 + 1, num_bins - 1)
    frac = pos - j0.astype(jnp.float32)
    u0 = stacked[pattern_id, j0, state]
    u1 = stacked[pattern_id, j1, state]
    return u0 * (1.0 - frac) + u1 * frac
