"""The chip's memory peak and the bytes the block kernel must move.

Peak: Google Cloud documentation, "TPU v5e" (per chip: 16 GB HBM at
819 GB/s), as the program's ``launch/mesh.py::PEAKS`` keeps it.  A device
kind that is not listed is an error, never a default.

The kernel's share of its roofline is held against HBM bandwidth alone.
Its operations are 32-bit integer and float selects on the vector unit,
for which Google publishes no peak (only the matrix unit's bf16 and int8
rates), so no compute side is counted: the share says how far the kernel
is from streaming its bytes, and nothing about its arithmetic.

The count describes what the fused per-event operator must move, whatever
implements it (it replaces ``launch/roofline.py::engine_block_intensity``,
which left out the emitted match columns and the ANY planes).  The kernel
keeps a dense store of ``P x N`` slots and a block of ``W`` events reads
and writes it once.  Bytes per block: the store in and out (active,
state, open, bind as 4-byte words; the A id planes for ANY queries), the
block's event columns in (class, bind and open per query; id, uniform,
E-BL priority, arrival), its rows out (latency, PM count, shed, drop), the
emitted match columns out (open and bind per slot per event), the
per-query transition statistics in and out, the utility tables in
(pSPICE), and one latency sample (two words) per event.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": dict(hbm_bytes_per_s=819e9),
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}") from None


def block_step_bytes(P: int, N: int, M: int, A: int, W: int, B: int,
                     any_kind: bool, emit: bool, stats: bool,
                     pspice: bool) -> int:
    """Bytes one block of ``W`` events moves."""
    store = P * N * 4 * 4 + (P * N * A * 4 if any_kind else 0)
    events_in = W * (3 * P * 4 + 4 * 4)
    rows_out = W * 4 * 4
    emitted = W * P * N * 4 * 2 if emit else 0
    stats_io = 2 * (2 * P * M * M * 4) if stats else 0
    tables = P * B * M * 4 if pspice else 0
    lat = W * 2 * 4
    return (2 * store + events_in + rows_out + emitted + stats_io + tables
            + lat)


def roofline(bytes_per_block: int, events: int, W: int, kernel_s: float,
             pk: dict) -> dict:
    """Share of the memory roofline: the least time HBM needs for the
    counted bytes, over the kernel's measured device time."""
    byts = bytes_per_block * (events / W)
    return dict(bytes=byts,
                share_pct=100.0 * byts / pk["hbm_bytes_per_s"] / kernel_s)


def for_config(cfg, model_bins: int) -> int:
    """``block_step_bytes`` from a program ``EngineConfig``'s shapes."""
    return block_step_bytes(
        P=cfg.num_patterns, N=cfg.max_pms, M=cfg.max_states,
        A=cfg.max_any_ids, W=cfg.block_events, B=model_bins,
        any_kind=cfg.kinds != "seq", emit=cfg.emit_matches,
        stats=cfg.gather_stats, pspice=cfg.shedder == "pspice")
