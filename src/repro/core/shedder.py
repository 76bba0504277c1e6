"""Load shedders (paper §III-F Algorithm 2 + §IV-A baselines).

All shedders operate on the dense PM store of the vectorized CEP operator:
    active     (N,) bool   — live PM mask
    pattern_id (N,) int32  — which query each PM belongs to
    state      (N,) int32  — current state machine state
    r_w        (N,) int32  — events remaining in the PM's window
Dropping a PM == clearing its mask bit; no payload movement (TPU adaptation
of Alg. 2's sort-and-remove, see DESIGN.md §3).

Shedders:
  - pspice_drop:  utility-table lookup (O(1)/PM) + keep-top-(n-ρ) by utility.
  - random_drop:  PM-BL — Bernoulli-uniform ρ-subset drop.
  - (E-BL, the event-level baseline, lives in the engine's input path —
     see repro/cep/engine.py — because it sheds events, not PMs.)

Selection plans (DESIGN.md §3, §8):
  - "threshold" (default): ``threshold_drop_mask`` — an O(N)
    histogram-refinement select.  No sort anywhere on the hot path.
  - "sort": the original argsort rank (kept as the oracle the threshold
    plan is property-tested against, and as the legacy baseline
    ``benchmarks/bench_engine.py`` measures the win over).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import utility as util

Array = jax.Array

# Finite inactive-slot sentinel (f32-safe inf).  A PYTHON float on
# purpose: a module-level jnp array would be a captured constant inside
# the block megakernel's Pallas trace (kernels/block_step.py runs
# ``threshold_drop_mask`` in-kernel); a weak scalar inlines, and
# promotes to the identical f32 value.
_BIG = 3.4e38


def pspice_utilities(stacked_tables: Array, bin_sizes: Array,
                     active: Array, pattern_id: Array, state: Array,
                     r_w: Array) -> Array:
    """Utility per PM slot; inactive slots get +inf so they are never chosen
    as 'lowest utility' (they aren't droppable — already empty)."""
    u = util.multi_pattern_lookup(stacked_tables, bin_sizes, pattern_id,
                                  state, r_w)
    return jnp.where(active, u, jnp.inf)


def drop_lowest_utility(active: Array, utilities: Array, rho: Array) -> Array:
    """Algorithm 2 ORACLE: drop the rho active PMs with the lowest utilities.

    Vectorized equivalent of sort + drop-first-ρ: rank PMs by utility
    ascending; clear slots whose rank < ρ.  rho is a traced scalar so this is
    jit/scan-safe (no dynamic shapes).  O(N log N) — the per-event hot path
    uses ``threshold_drop_mask`` instead; this stays as the property-test
    oracle and the legacy plan (``plan="sort"``).
    """
    order = jnp.argsort(utilities)                # ascending; inf (inactive) last
    ranks = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    drop = ranks < rho
    return active & ~drop


def bucket_edges(lo: Array, hi: Array, nbins: int) -> Array:
    """The (nbins+1,) bucket edges every histogram implementation shares.

    The SAME expression is used by the jnp histogram below and by the
    Pallas kernel (``kernels.shed_select.utility_histogram_pallas``), so
    boundary values land in the same bucket bit-for-bit on every backend.
    The top edge is +inf: the last bucket is right-closed (it owns the max).
    """
    edges = lo + (hi - lo) * jnp.arange(nbins + 1, dtype=jnp.float32) / nbins
    return edges.at[-1].set(jnp.inf)


def _histogram_jnp(u: Array, mask: Array, lo: Array, hi: Array,
                   nbins: int) -> Array:
    """O(N) masked bucket counts via one scatter-add.  Bucket membership is
    edge-comparison based (searchsorted against ``bucket_edges``) so it
    agrees exactly with the Pallas histogram kernel."""
    edges = bucket_edges(lo, hi, nbins)
    b = jnp.clip(jnp.searchsorted(edges, jnp.where(mask, u, lo),
                                  side="right") - 1, 0, nbins - 1)
    return jnp.zeros((nbins,), jnp.int32).at[b].add(mask.astype(jnp.int32))


def threshold_drop_mask(active: Array, utilities: Array, rho: Array, *,
                        nbins: int = 128, levels: int = 3,
                        hist_fn=None) -> Array:
    """Algorithm 2 without the sort: O(N·levels) histogram-refinement select.

    Each level buckets the surviving candidate set over [lo, hi), finds the
    boundary bucket that contains the ρ-th lowest utility (cumsum over the
    tiny histogram + searchsorted), drops everything strictly below it, and
    recurses INTO the bucket.  After ``levels`` rounds the candidate span is
    (hi-lo)/nbins**levels wide; the remaining budget breaks ties by slot
    index — exactly the stable-argsort oracle's tie order once the bucket
    has collapsed to a single f32 value (all-ties inputs are bitwise equal
    to the oracle).  Guarantees, for any input (tests/test_shedder.py):
      - exactly min(ρ, n_active) PMs dropped,
      - inactive slots never revived,
      - max(dropped utility) ≤ min(kept utility) + (hi-lo)/nbins**levels.

    ``hist_fn(u, lo, hi) -> (nbins,) int32`` may be supplied to compute the
    bucket counts (the Pallas-kernel path passes
    ``utility_histogram_pallas``); excluded entries are passed as NaN, which
    no bucket counts.  The default is one jnp scatter-add; both agree
    bitwise because they share ``bucket_edges``.
    """
    u = utilities.astype(jnp.float32)
    n_active = active.sum().astype(jnp.int32)
    need = jnp.minimum(rho.astype(jnp.int32), n_active)
    lo = jnp.min(jnp.where(active, u, _BIG))
    hi0 = jnp.max(jnp.where(active, u, -_BIG))
    hi = jnp.where(hi0 > lo, hi0, lo + 1.0)
    mask = active
    drop = jnp.zeros_like(active)
    for _ in range(levels):
        if hist_fn is None:
            hist = _histogram_jnp(u, mask, lo, hi, nbins)
        else:
            hist = hist_fn(jnp.where(mask, u, jnp.nan), lo, hi)
        cum = jnp.cumsum(hist)
        # First bucket whose cumulative count reaches the remaining budget.
        kb = jnp.clip(jnp.searchsorted(cum, need, side="left"), 0, nbins - 1)
        # Boundary values MUST compare against the very same f32 edge the
        # histogram bucketed them with — take it from the shared edges.
        edges = bucket_edges(lo, hi, nbins)
        edge = edges[kb]
        upper = edges[kb + 1]                 # +inf for the last bucket
        below = mask & (u < edge)
        drop = drop | below
        need = jnp.maximum(need - below.sum().astype(jnp.int32), 0)
        mask = mask & ~below & (u < upper)
        lo = edge
        hi_next = jnp.where(kb == nbins - 1, hi, upper)
        hi = jnp.where(hi_next > lo, hi_next, lo + 1.0)
    # Exact-ρ remainder inside the final bucket: first `need` by slot index.
    idx_rank = jnp.cumsum(mask) - 1
    drop = drop | (mask & (idx_rank < need))
    return active & ~drop


def threshold_drop_mask_rows(active: Array, utilities: Array, rho: Array, *,
                             nbins: int = 128, levels: int = 3) -> Array:
    """``threshold_drop_mask`` over a (R, N) store, flat order row-major,
    written in operations the TPU kernel compiler lowers (no scatter,
    searchsorted or cumsum) — the block kernel's in-kernel Algorithm 2.

    Bitwise the same drop mask as ``threshold_drop_mask`` on the
    flattened arrays: each level's cumulative bucket counts are counted
    directly against the shared edge expression of ``bucket_edges``
    (``cum[b] = #{u < edges[b+1]}``, since every candidate lies at or
    above ``edges[0] = lo``), the boundary bucket is ``#{b: cum[b] <
    need}`` (``searchsorted`` left), and the slot-order remainder takes
    the ``need`` lowest flat positions by a binary search on the count of
    candidates below a position instead of a running rank.
    """
    f32, i32 = jnp.float32, jnp.int32
    R, N = active.shape
    u = utilities.astype(f32)
    n_active = jnp.sum(active.astype(i32))
    need = jnp.minimum(rho.astype(i32), n_active)
    lo = jnp.min(jnp.where(active, u, _BIG))
    hi0 = jnp.max(jnp.where(active, u, -_BIG))
    hi = jnp.where(hi0 > lo, hi0, lo + 1.0)
    b_up = jax.lax.broadcasted_iota(i32, (nbins, 1), 0) + 1   # b + 1
    mask = active
    drop = jnp.zeros_like(active)

    def edge_at(lo, hi, k):
        # ``bucket_edges(lo, hi, nbins)[k]``: the same f32 expression.
        return lo + (hi - lo) * k.astype(f32) / nbins

    for _ in range(levels):
        upper_edges = jnp.where(b_up == nbins, jnp.inf,
                                edge_at(lo, hi, b_up))      # (nbins, 1)
        cum = jnp.zeros((nbins, 1), i32)
        for r in range(R):
            below_b = (u[r:r + 1, :] < upper_edges) & mask[r:r + 1, :]
            cum = cum + jnp.sum(below_b.astype(i32), axis=1, keepdims=True)
        kb = jnp.minimum(jnp.sum((cum < need).astype(i32)), nbins - 1)
        edge = edge_at(lo, hi, kb)
        upper = jnp.where(kb == nbins - 1, jnp.inf, edge_at(lo, hi, kb + 1))
        below = mask & (u < edge)
        drop = drop | below
        need = jnp.maximum(need - jnp.sum(below.astype(i32)), 0)
        mask = mask & ~below & (u < upper)
        lo = edge
        hi_next = jnp.where(kb == nbins - 1, hi, upper)
        hi = jnp.where(hi_next > lo, hi_next, lo + 1.0)
    # Exact-ρ remainder: the `need` lowest flat positions left in `mask`.
    # Smallest t with #{mask & pos < t} >= need, by bisection on [0, R·N].
    pos = (jax.lax.broadcasted_iota(i32, (R, N), 0) * N +
           jax.lax.broadcasted_iota(i32, (R, N), 1))

    def bisect(_, lh):
        t_lo, t_hi = lh
        mid = (t_lo + t_hi) // 2
        ok = jnp.sum((mask & (pos < mid)).astype(i32)) >= need
        return jnp.where(ok, t_lo, mid + 1), jnp.where(ok, mid, t_hi)

    steps = max(1, (R * N).bit_length())
    _, t = jax.lax.fori_loop(0, steps, bisect, (jnp.int32(0), jnp.int32(R * N)))
    drop = drop | (mask & (pos < t))
    return active & ~drop


def random_drop(key: Array, active: Array, rho: Array) -> Array:
    """PM-BL: drop a uniformly random ρ-subset of active PMs — exactly ρ
    dropped, matching the budget the overload detector computed.  Realized
    as the O(N) threshold select over iid uniform scores (the ρ lowest of
    iid uniforms are a uniform ρ-subset); no sort."""
    scores = jax.random.uniform(key, active.shape)
    return threshold_drop_mask(active, scores, rho)


def shed(kind: str, *, key: Array, active: Array, rho: Array,
         stacked_tables: Array | None = None, bin_sizes: Array | None = None,
         pattern_id: Array | None = None, state: Array | None = None,
         r_w: Array | None = None, plan: str = "threshold") -> Array:
    """Dispatch helper used by the engine. kind in {'pspice', 'pmbl'};
    plan in {'threshold', 'sort'} (see module docstring)."""
    if kind == "pspice":
        u = pspice_utilities(stacked_tables, bin_sizes, active, pattern_id,
                             state, r_w)
        if plan == "sort":
            return drop_lowest_utility(active, u, rho)
        return threshold_drop_mask(active, u, rho)
    if kind == "pmbl":
        if plan == "sort":
            scores = jax.random.uniform(key, active.shape)
            scores = jnp.where(active, scores, jnp.inf)
            return drop_lowest_utility(active, scores, rho)
        return random_drop(key, active, rho)
    raise ValueError(f"unknown shedder kind: {kind}")


# ---------------------------------------------------------------------------
# E-BL event-utility model (paper §IV-A baseline 2, after He et al. [15] +
# weighted sampling [13]).  Event *types* get utility proportional to their
# repetition in patterns and in windows; low-utility types are dropped from
# incoming windows by uniform sampling within type.
# ---------------------------------------------------------------------------

def ebl_type_utilities(pattern_class_of_type: Array,
                       class_repetition_in_patterns: Array,
                       type_frequency_in_windows: Array) -> Array:
    """Utility per event type.

    pattern_class_of_type: (n_types,) int32 — pattern class each raw event
        type maps to (0 == irrelevant to every pattern).
    class_repetition_in_patterns: (n_classes,) float — how often the class
        appears across pattern definitions (importance ∝ repetition).
    type_frequency_in_windows: (n_types,) float — empirical frequency (types
        that are rare in windows are harder to replace → more valuable).
    """
    rep = class_repetition_in_patterns[pattern_class_of_type]
    freq = jnp.maximum(type_frequency_in_windows, 1e-9)
    u = rep / freq
    return jnp.where(pattern_class_of_type > 0, u, 0.0)


def ebl_drop_mask(key: Array, type_of_event: Array, type_utils: Array,
                  drop_fraction: Array) -> Array:
    """Per-event drop decision: drop probability inversely related to the
    event type's utility, scaled so the expected drop rate == drop_fraction.

    Returns bool (n_events,) — True means the event is dropped before window
    processing (black-box shedding)."""
    u = type_utils[type_of_event]
    u_max = jnp.maximum(u.max(), 1e-9)
    # Normalized "keep priority" in [0, 1]; uniform sampling within a type.
    keep_priority = u / u_max
    # Drop probability per event, renormalized to hit the global budget.
    raw = 1.0 - keep_priority
    mean_raw = jnp.maximum(raw.mean(), 1e-9)
    p_drop = jnp.clip(raw * (drop_fraction / mean_raw), 0.0, 1.0)
    return jax.random.uniform(key, type_of_event.shape) < p_drop
