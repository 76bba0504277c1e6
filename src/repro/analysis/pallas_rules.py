"""Pallas-specific checks (DESIGN.md §11): BlockSpecs vs kernels/tiling.

Walks a traced jaxpr for ``pallas_call`` equations and validates each
launch's grid/BlockSpec geometry against the repo's tiling contract
(``kernels/tiling.py``) without executing anything:

* tile-multiple — every operand's array shape is an exact multiple of
  its block shape (callers must pad with ``tiling.pad_to_tile``; a
  non-multiple means a partial edge tile the kernels don't mask for);
* grid-bounds — evaluating each BlockSpec's ``index_map`` at the grid
  corners must keep ``offset x block`` inside the array;
* vmem-budget — the per-generation resident footprint (sum of one
  block per operand/result) stays under the per-core VMEM budget;
* block-alias — the store-resident ``block_step`` launch carries its
  ``input_output_aliases`` (the in-place store update PR 5 depends on);
* kernel-census — the INNER kernel jaxpr contains no banned primitive
  (a sort inside a Pallas body would evade the HLO text check, since
  Mosaic lowers it outside XLA's op vocabulary).

These run on the same artifacts as ``rules.RULES`` — they just no-op on
cells whose jaxpr launches no Pallas kernel (backend="xla").
"""
from __future__ import annotations

import math

import jax

from repro.analysis import rules as R

# ~16 MiB of VMEM per TensorCore (see /opt/skills/guides notes); one
# kernel generation must keep every operand/result block resident.
VMEM_BUDGET_BYTES = 16 * 1024 * 1024

# The block_step megakernel aliases these store buffers in place
# (kernels/block_step.py): active, state, open_idx, bind, ring, ring_ptr,
# complex_count, pms_created, lat_n, lat_l (plus idset for ANY patterns
# and the observation matrices while gathering stats).
BLOCK_STEP_MIN_ALIASES = 10

_BANNED_INNER = ("sort", "pure_callback", "io_callback", "debug_callback")


def pallas_calls(jaxpr) -> list:
    """All pallas_call eqns in the jaxpr, including nested sub-jaxprs."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for v in eqn.params.values():
                for sub in R._sub_jaxprs(v):
                    walk(sub)
    walk(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return found


def _kernel_name(eqn) -> str:
    return eqn.params.get("name") or "pallas_call"


def _block_dims(bm) -> tuple:
    """Block sizes per dim: ``Blocked``/``Element`` carry a size, a
    ``Squeezed`` dim is one element."""
    return tuple(int(getattr(d, "block_size", 1) if not isinstance(d, int)
                     else d) for d in bm.block_shape)


def _block_bytes(bm) -> int:
    shape = _block_dims(bm)
    dt = bm.array_aval.dtype
    return math.prod(shape) * dt.itemsize if shape else dt.itemsize


def _grid_corners(grid):
    """Index tuples to probe: all points for tiny grids, else corners."""
    if not grid:
        return [()]
    if math.prod(grid) <= 64:
        pts = [()]
        for g in grid:
            pts = [p + (i,) for p in pts for i in range(g)]
        return pts
    corners = [()]
    for g in grid:
        corners = [p + (i,) for p in corners
                   for i in ({0, g - 1} if g > 1 else {0})]
    return corners


def _eval_index_map(bm, idx):
    jx = bm.index_map_jaxpr
    out = jax.core.eval_jaxpr(jx.jaxpr, jx.consts, *map(int, idx))
    return tuple(int(v) for v in out)


def check_pallas_calls(art: R.Artifact, ctr) -> list:
    """The Pallas findings for one artifact (empty-jaxpr safe)."""
    if art.jaxpr is None:
        return []
    calls = pallas_calls(art.jaxpr)
    is_block_cfg = getattr(art.cfg, "backend", "") == "pallas_block"
    if not calls:
        if is_block_cfg:
            return [R.Finding(
                "pallas-block-alias", False,
                "backend=pallas_block but no block kernel launch found",
                art.name)]
        return [R.Finding("pallas", True, "no pallas_call in jaxpr",
                          art.name)]
    out = []
    saw_block_step = False
    for eqn in calls:
        name = _kernel_name(eqn)
        gm = eqn.params["grid_mapping"]
        grid = tuple(int(g) for g in gm.grid)
        bms = list(gm.block_mappings)

        # -- tile-multiple + grid-bounds per operand ---------------------
        bad_tile, bad_bounds = [], []
        for k, bm in enumerate(bms):
            ashape = tuple(int(d) for d in bm.array_aval.shape)
            bshape = _block_dims(bm)
            if len(ashape) != len(bshape):
                bad_tile.append(f"op{k}: rank {ashape} vs block {bshape}")
                continue
            if any(b and a % b for a, b in zip(ashape, bshape)):
                bad_tile.append(f"op{k}: array {ashape} not a multiple "
                                f"of block {bshape}")
            try:
                for idx in _grid_corners(grid):
                    off = _eval_index_map(bm, idx)
                    for o, b, a in zip(off, bshape, ashape):
                        if o * b < 0 or (o + 1) * b > a:
                            bad_bounds.append(
                                f"op{k}@grid{idx}: block [{o * b},"
                                f"{(o + 1) * b}) outside [0,{a})")
            except Exception as e:  # index_map not statically evaluable
                bad_bounds.append(f"op{k}: index_map eval failed: {e}")
        out.append(R.Finding(
            "pallas-tiling", not bad_tile,
            bad_tile[0] if bad_tile else
            f"{name}: {len(bms)} operands tile-aligned, grid {grid}",
            art.name))
        out.append(R.Finding(
            "pallas-grid-bounds", not bad_bounds,
            bad_bounds[0] if bad_bounds else
            f"{name}: index maps in-bounds at "
            f"{len(_grid_corners(grid))} grid point(s)", art.name))

        # -- VMEM: one generation = one block per operand ----------------
        vmem = sum(_block_bytes(bm) for bm in bms)
        out.append(R.Finding(
            "pallas-vmem", vmem <= VMEM_BUDGET_BYTES,
            f"{name}: resident blocks {vmem} B vs budget "
            f"{VMEM_BUDGET_BYTES} B", art.name))

        # -- inner kernel census -----------------------------------------
        inner = R.primitive_census(eqn.params["jaxpr"])
        hit = [p for p in _BANNED_INNER if inner.get(p, 0)]
        out.append(R.Finding(
            "pallas-kernel-census", not hit,
            f"{name}: banned primitive(s) {hit} inside kernel body"
            if hit else f"{name}: kernel body clean "
            f"({sum(inner.values())} eqns)", art.name))

        # -- block_step alias coverage ------------------------------------
        if "block" in name:
            saw_block_step = True
            aliases = eqn.params.get("input_output_aliases") or ()
            ok = len(aliases) >= BLOCK_STEP_MIN_ALIASES
            out.append(R.Finding(
                "pallas-block-alias", ok,
                f"{name}: {len(aliases)} input_output_aliases "
                f"(store-resident update needs >= "
                f"{BLOCK_STEP_MIN_ALIASES})", art.name))
    if is_block_cfg and not saw_block_step:
        out.append(R.Finding(
            "pallas-block-alias", False,
            "backend=pallas_block but no block kernel launch found",
            art.name))
    return out


PALLAS_RULE = R.Rule(
    "pallas", "PR 5",
    "Pallas launches match kernels/tiling.py: tile-multiple shapes, "
    "in-bounds index maps, VMEM-resident generations, aliased "
    "block_step stores, clean kernel bodies.",
    check_pallas_calls)
