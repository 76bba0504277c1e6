"""Shared block-tiling helpers for the Pallas kernels.

Every kernel in this package accepts stores whose length is NOT a tile
multiple: inputs pad the tail with neutral fill values (inactive slots,
NaN utilities) that the kernel provably passes through, and outputs slice
back.  The padding arithmetic used to be repeated per kernel; this module
is the one owner (used by nfa_transition.py, shed_select.py and the
block-step megakernel's event-axis padding in cep/engine.py).
"""
from __future__ import annotations

import jax.numpy as jnp

LANES = 128   # TPU vector lane width: the last dim of every 2-D tile


def tile_pad(tile: int, n: int) -> int:
    """Elements of tail padding needed to reach a multiple of ``tile``."""
    return (-n) % tile


def pad_to_tile(tile: int, *pairs):
    """Pad each ``(array, fill)`` pair's axis 0 to a multiple of ``tile``.

    Returns ``(padded_0, ..., padded_k, pad)`` where ``pad`` is the tail
    length that callers slice back off their outputs (0 when the length
    already divides — arrays pass through untouched).
    """
    n = pairs[0][0].shape[0]
    pad = tile_pad(tile, n)
    if not pad:
        return tuple(x for x, _ in pairs) + (0,)
    padded = tuple(
        jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
        for x, fill in pairs)
    return padded + (pad,)
