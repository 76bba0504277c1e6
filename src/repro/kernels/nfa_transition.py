"""Pallas TPU kernel for the CEP operator's hot loop: advancing every
active partial match against one incoming event (paper §III / engine step 4).

TPU adaptation: the per-PM table lookup ``next = trans[state, class]`` is a
data-dependent gather — hostile to the VPU.  The kernel instead selects
over the (tiny, ≤32-entry) column ``trans_col[s] = trans[s, class]`` for
the incoming event's class, held in SMEM: ``next = Σ_s [state == s] ·
trans_col[s]`` as M vector selects, exact for any table, fused with the
binding check and completion detection.

Grid: PM tiles of ``tile`` slots, laid out (tile/128, 128) so every block
matches the TPU's (8, 128) vector tiling; trans_col/bind/final ride in SMEM.

Compiled for TPU (``tests/test_tpu_compile.py``); run interpreted off-TPU
and checked against ``ref.nfa_advance_ref`` (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import LANES, pad_to_tile

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _nfa_kernel(state_ref, bind_ref, active_ref, tcol_ref, scal_ref,
                newstate_ref, completed_ref, *, m: int):
    state = state_ref[...]                    # (rows, 128) int32
    bind = bind_ref[...]
    live = active_ref[...] > 0
    ev_bind, final, use_binding = scal_ref[0], scal_ref[1], scal_ref[2]
    nxt = jnp.zeros_like(state)
    for s in range(m):
        nxt = jnp.where(state == s, tcol_ref[s], nxt)
    bind_ok = (bind == ev_bind) | (use_binding == 0)
    nxt = jnp.where(live & bind_ok, nxt, state)
    completed = live & (nxt == final) & (state != final)
    newstate_ref[...] = nxt
    completed_ref[...] = completed.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("tile", "interpret"))
def nfa_advance_pallas(state: jax.Array, bind: jax.Array, active: jax.Array,
                       trans_col: jax.Array, ev_bind, final, use_binding,
                       *, interpret: bool, tile: int = 1024):
    """Advance all PMs against one event.

    state/bind: (N,) int32; active: (N,) bool; trans_col: (M,) int32 —
    trans[:, class] for the event's class.  Returns (new_state (N,),
    completed (N,) bool).  ``tile`` is a multiple of 1024 (8 × 128).

    N need not be a tile multiple: inputs pad with INACTIVE slots (state 0,
    bind -1, active 0 — the kernel passes them through untouched and never
    flags completion) and the outputs slice back, matching the treatment
    the shed kernels give non-tile-multiple stores."""
    N = state.shape[0]
    m = trans_col.shape[0]
    state, bind, active, pad = pad_to_tile(
        tile, (state, 0), (bind, -1), (active.astype(jnp.int32), 0))
    rows, blk = (N + pad) // LANES, tile // LANES
    scal = jnp.array([ev_bind, final, use_binding], jnp.int32)
    spec = pl.BlockSpec((blk, LANES), lambda i: (i, 0))
    new_state, completed = pl.pallas_call(
        functools.partial(_nfa_kernel, m=m),
        grid=(rows // blk,),
        in_specs=[spec, spec, spec, _SMEM, _SMEM],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, LANES), jnp.int32)] * 2,
        interpret=interpret,
    )(*(x.reshape(rows, LANES) for x in (state, bind, active)),
      trans_col.astype(jnp.int32), scal)
    new_state, completed = new_state.reshape(-1), completed.reshape(-1)
    if pad:
        new_state, completed = new_state[:N], completed[:N]
    return new_state, completed.astype(bool)
