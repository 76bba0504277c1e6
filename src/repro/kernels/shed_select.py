"""Pallas TPU kernels for the load shedder (paper Algorithm 2).

Two kernels replace the sort in Alg. 2 with a histogram-threshold plan
(O(N) instead of O(N log N), and VMEM-tiled):

  1. ``utility_lookup``: fused UT-table lookup with linear interpolation —
     the two (bin, state) table reads are vector selects against the
     (bins × states) utility table held in SMEM (O(1) per PM, the property
     the paper highlights; exact for any table).
  2. ``utility_histogram``: per-tile bucket counts accumulated across the
     grid — the driver (ops.shed_lowest_pallas) runs a cumsum over the tiny
     histogram to pick the drop threshold τ such that ~ρ PMs fall below it,
     then a final compare produces the drop mask (exact-ρ tie-break happens
     on the ≤1-bucket remainder).

These are the STANDALONE kernels the per-event ``backend="pallas"``
path dispatches through ``ops.shed_lowest_threshold``.  The block
megakernel (kernels/block_step.py) does not call them: its fused fire
path runs the same select (``shedder.threshold_drop_mask_rows``) against
the same ``bucket_edges`` inside the block kernel — one shared bucketing
expression is what keeps every backend's drop mask bitwise identical.

PM vectors are laid out (N/128, 128) and tiled in blocks of ``tile``
slots (a multiple of 8 × 128), matching the TPU's vector tiling.
Compiled for TPU (``tests/test_tpu_compile.py``); run interpreted
off-TPU and checked against core.shedder / kernels.ref (tests/).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.shedder import bucket_edges
from repro.kernels.tiling import LANES, pad_to_tile

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _lookup_kernel(state_ref, rw_ref, active_ref, table_ref, bs_ref,
                   out_ref, *, num_bins: int, m: int, inf_val: float):
    state = state_ref[...]
    rw = rw_ref[...].astype(jnp.float32)
    active = active_ref[...] > 0
    bin_size = bs_ref[0]                      # traced f32 scalar

    pos = jnp.clip(rw / bin_size - 1.0, 0.0, num_bins - 1.0)
    j0 = jnp.floor(pos).astype(jnp.int32)
    j1 = jnp.minimum(j0 + 1, num_bins - 1)
    frac = pos - j0.astype(jnp.float32)
    s_hit = [state == s for s in range(m)]

    def per_bin(b, u01):
        u0, u1 = u01
        for s in range(m):
            val = table_ref[b, s]
            u0 = jnp.where((j0 == b) & s_hit[s], val, u0)
            u1 = jnp.where((j1 == b) & s_hit[s], val, u1)
        return u0, u1

    zero = jnp.zeros(state.shape, jnp.float32)
    u0, u1 = jax.lax.fori_loop(0, num_bins, per_bin, (zero, zero))
    u = u0 * (1.0 - frac) + u1 * frac
    out_ref[...] = jnp.where(active, u, inf_val)


def utility_lookup_dyn_pallas(state, r_w, active, table, bin_size, *,
                              interpret: bool, tile: int = 1024,
                              inf_val: float = 3.4e38):
    """``utility_lookup_pallas`` with a TRACED bin size (f32 scalar array)
    — the engine's multi-pattern dispatch passes ``model.ut_bins[p]``, a
    device value, so the bin size rides into the kernel as a (1,) scalar
    input instead of a static Python int.
    """
    N = state.shape[0]
    num_bins, m = table.shape
    state, r_w, active, pad = pad_to_tile(
        tile, (state, 0), (r_w, 1), (active.astype(jnp.int32), 0))
    rows, blk = (N + pad) // LANES, tile // LANES
    spec = pl.BlockSpec((blk, LANES), lambda i: (i, 0))
    bs = jnp.asarray(bin_size, jnp.float32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_lookup_kernel, num_bins=num_bins, m=m,
                          inf_val=inf_val),
        grid=(rows // blk,),
        in_specs=[spec, spec, spec, _SMEM, _SMEM],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
    )(*(x.reshape(rows, LANES) for x in (state, r_w, active)),
      table.astype(jnp.float32), bs).reshape(-1)
    return out[:N] if pad else out


@functools.partial(jax.jit, static_argnames=("bin_size", "tile",
                                             "interpret"))
def utility_lookup_pallas(state, r_w, active, table, *, bin_size: int,
                          interpret: bool, tile: int = 1024,
                          inf_val: float = 3.4e38):
    """Fused O(1)-per-PM utility lookup. table: (num_bins, M) f32.

    N need not be a tile multiple: inputs are padded with inactive slots
    (which lower to inf_val in the kernel) and the output is sliced back.
    """
    return utility_lookup_dyn_pallas(state, r_w, active, table,
                                     jnp.float32(bin_size), tile=tile,
                                     interpret=interpret, inf_val=inf_val)


def _hist_kernel(u_ref, edges_ref, hist_ref, *, nbins: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    u = u_ref[...]                            # (rows, 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, nbins), 1)

    def per_bucket(b, acc):
        inb = (u >= edges_ref[b]) & (u < edges_ref[b + 1])
        return jnp.where(lane == b, acc + jnp.sum(inb.astype(jnp.int32)),
                         acc)

    hist_ref[...] += jax.lax.fori_loop(0, nbins, per_bucket,
                                       jnp.zeros((1, nbins), jnp.int32))


@functools.partial(jax.jit, static_argnames=("nbins", "tile", "interpret"))
def utility_histogram_pallas(u, lo, hi, *, interpret: bool, nbins: int = 64,
                             tile: int = 1024):
    """Bucket counts of u within [lo, hi) — the threshold-plan input.

    N need not be a tile multiple: the tail pads with NaN, which fails
    both bucket comparisons and is therefore never counted.
    """
    N = u.shape[0]
    u, pad = pad_to_tile(tile, (u, jnp.nan))
    rows, blk = (N + pad) // LANES, tile // LANES
    # Shared edge expression (core.shedder.bucket_edges): boundary values
    # bucket identically on the jnp and Pallas histogram paths.
    edges = bucket_edges(lo, hi, nbins)
    return pl.pallas_call(
        functools.partial(_hist_kernel, nbins=nbins),
        grid=(rows // blk,),
        in_specs=[pl.BlockSpec((blk, LANES), lambda i: (i, 0)), _SMEM],
        out_specs=pl.BlockSpec((1, nbins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, nbins), jnp.int32),
        interpret=interpret,
    )(u.reshape(rows, LANES), edges)[0]
