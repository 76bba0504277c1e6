"""Jit'd public wrappers around the Pallas kernels + the engine's
backend-dispatch surface (``EngineConfig.backend`` — DESIGN.md §8).

The engine never touches a kernel directly: it calls
``advance_seq_multi`` / ``pm_utilities_multi`` / ``shed_lowest_threshold``
below, which run the Pallas kernels (compiled on TPU, interpreted
everywhere else via :func:`default_interpret`) and are bitwise-equivalent
to the jnp reference path — the one-hot matmuls touch exactly one nonzero
per row, and the histogram-threshold driver shares ``bucket_edges`` with
the jnp histogram, so xla-vs-pallas engine runs compare equal
(tests/test_backend.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import shedder as shd
from repro.kernels.block_step import block_step  # noqa: F401
from repro.kernels.flash_attention import flash_attention_pallas  # noqa: F401
from repro.kernels.nfa_transition import nfa_advance_pallas  # noqa: F401
from repro.kernels.shed_select import (utility_histogram_pallas,
                                       utility_lookup_dyn_pallas,
                                       utility_lookup_pallas)
from repro.kernels.tiling import pad_to_tile, tile_pad  # noqa: F401


def default_interpret() -> bool:
    """Pallas kernels compile only on TPU; anywhere else run interpreted.

    Every kernel entry point takes ``interpret`` without a default: the
    engine passes this, so a TPU run can never fall back to the
    interpreter unnoticed."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("bin_size", "nbins",
                                             "interpret"))
def shed_lowest_pallas(active: jax.Array, state: jax.Array, r_w: jax.Array,
                       table: jax.Array, rho: jax.Array, *, bin_size: int,
                       nbins: int = 64, interpret: bool) -> jax.Array:
    """Algorithm 2 via kernels: utility lookup → histogram-refinement
    threshold plan (``core.shedder.threshold_drop_mask`` with the Pallas
    histogram as its bucket counter).  O(N) end to end — the former
    exact-ρ argsort inside the boundary bucket is gone; remaining ties
    break by slot index after the refinement levels collapse the bucket.

    Returns the new active mask with the ρ lowest-utility PMs cleared.
    """
    u = utility_lookup_pallas(state, r_w, active, table, bin_size=bin_size,
                              interpret=interpret)
    hist = functools.partial(utility_histogram_pallas, nbins=nbins,
                             interpret=interpret)
    return shd.threshold_drop_mask(active, u, rho, nbins=nbins, hist_fn=hist)


def advance_seq_multi(state: jax.Array, bind: jax.Array, active: jax.Array,
                      trans: jax.Array, ev_class: jax.Array,
                      ev_bind: jax.Array, final_state: jax.Array,
                      uses_binding: jax.Array, *,
                      interpret: bool) -> jax.Array:
    """SEQ advance for the whole (P, N) PM store via ``nfa_advance_pallas``,
    one kernel launch per pattern (P is small and static).

    ``trans_col = trans[p, :, class_p]`` is gathered outside the kernel
    (tiny: (M,) per pattern); binding check + advance + the one-hot MXU
    matmul run inside.  Returns new_state (P, N) int32 — completions are
    detected by the engine from (old, new) states, same as the jnp path.
    """
    P = state.shape[0]
    out = []
    for p in range(P):
        tcol = jnp.take(trans[p], ev_class[p], axis=1)      # (M,)
        ns, _ = nfa_advance_pallas(state[p], bind[p], active[p], tcol,
                                   ev_bind[p], final_state[p],
                                   uses_binding[p].astype(jnp.int32),
                                   interpret=interpret)
        out.append(ns)
    return jnp.stack(out)


def pm_utilities_multi(state: jax.Array, r_w: jax.Array, active: jax.Array,
                       tables: jax.Array, bin_sizes: jax.Array, *,
                       interpret: bool) -> jax.Array:
    """Fused utility lookup for the whole (P, N) store: one
    ``utility_lookup_dyn_pallas`` launch per pattern against its own
    (B, M) table and traced bin size.  Inactive slots get the kernel's
    finite +inf sentinel; the threshold driver masks them anyway.
    """
    P = state.shape[0]
    return jnp.stack([
        utility_lookup_dyn_pallas(state[p], r_w[p], active[p], tables[p],
                                  bin_sizes[p], interpret=interpret)
        for p in range(P)])


def shed_lowest_threshold(active: jax.Array, utilities: jax.Array,
                          rho: jax.Array, *, nbins: int = 128,
                          interpret: bool) -> jax.Array:
    """Histogram-threshold drop mask over flat (N,) utilities with the
    Pallas histogram kernel as the bucket counter (engine pallas path)."""
    hist = functools.partial(utility_histogram_pallas, nbins=nbins,
                             interpret=interpret)
    return shd.threshold_drop_mask(active, utilities, rho, nbins=nbins,
                                   hist_fn=hist)
