"""Roofline-term extraction with depth extrapolation.

XLA's HLO cost analysis counts each while-loop body ONCE (no trip-count
multiplication), so a rolled scan-over-layers under-reports FLOPs by ~L×.
We therefore lower each cell in ANALYSIS MODE (every scan unrolled, chunk
granularity coarsened FLOP-invariantly — models/settings.py) at two reduced
depths L1 < L2 and extrapolate linearly to the real depth:

    term(L) = term(L1) + (L - L1)/(L2 - L1) · (term(L2) - term(L1))

Layers are identical, so FLOPs/bytes/collective-bytes are affine in L; the
intercept captures embeddings, the LM head, and the loss.  For zamba2 the
depths are multiples of hybrid_attn_every so each delta contains exactly one
shared-attention application; whisper varies encoder and decoder depth
together (both 12 at target).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.configs.shapes import ShapeSpec
from repro.launch import hlo_analysis as HA
from repro.launch import mesh as M
from repro.models import settings as SET
from repro.models.config import ModelConfig


def engine_block_intensity(engine_cfg) -> dict:
    """Arithmetic-intensity estimate for the CEP per-event step: the
    unfused per-event scan vs the fused event-block kernel
    (kernels/block_step.py, DESIGN.md §10).

    XLA's HLO cost analysis counts a while-loop body once regardless of
    trip count AND cannot see VMEM residency (the fused kernel's whole
    point is that the store is loaded once per W events instead of once
    per event), so this is an analytic model, not an HLO readout:

      * the store is P·N slots; per event the operator runs ~14
        elementwise ops per slot (expire, advance lookup + selects,
        completion detect, spawn compaction, activity reductions);
      * the unfused step streams the five (P, N) store arrays (+ the
        (P, N, A) idset for ANY-capable pattern sets) from memory ~6
        times per event (advance, spawn, utility/overload bookkeeping
        read-modify-write pairs — the op inventory of DESIGN.md §8);
      * the fused kernel loads and stores the same arrays ONCE per
        W-event block, plus per-event row IO (StepOut columns and the
        classified event).

    Emitted into BENCH_engine.json by benchmarks/bench_engine.py so the
    perf trajectory records the memory-traffic claim next to the
    measured events/s.
    """
    P, N, A = (engine_cfg.num_patterns, engine_cfg.max_pms,
               engine_cfg.max_any_ids)
    W = engine_cfg.block_events
    any_capable = engine_cfg.kinds != "seq"
    store_bytes = P * N * (4 * 4 + 1)          # state/open/bind ×i32 + mask
    if any_capable:
        store_bytes += P * N * A * 4
    row_bytes = 4 * 4 + 8 * P * 4              # StepOut row + event columns
    ops_per_slot = 14.0
    flops_per_event = ops_per_slot * P * N
    unfused_passes = 6.0
    bytes_unfused = unfused_passes * store_bytes + row_bytes
    bytes_fused = 2.0 * store_bytes / W + row_bytes
    return {
        "store_bytes": store_bytes,
        "flops_per_event": flops_per_event,
        "bytes_per_event_unfused": bytes_unfused,
        "bytes_per_event_fused": bytes_fused,
        "intensity_unfused": flops_per_event / bytes_unfused,
        "intensity_fused": flops_per_event / bytes_fused,
        "traffic_ratio": bytes_unfused / bytes_fused,
        "block_events": W,
    }


def analysis_depths(cfg: ModelConfig) -> tuple[ModelConfig, ModelConfig,
                                               int, int, int]:
    """(cfg_L1, cfg_L2, L1, L2, L_target)."""
    if cfg.hybrid_attn_every:
        e = cfg.hybrid_attn_every
        l1, l2 = e, 2 * e
        c1 = dataclasses.replace(cfg, num_layers=l1)
        c2 = dataclasses.replace(cfg, num_layers=l2)
    elif cfg.enc_dec:
        l1, l2 = 2, 3
        c1 = dataclasses.replace(cfg, num_layers=l1, enc_layers=l1)
        c2 = dataclasses.replace(cfg, num_layers=l2, enc_layers=l2)
    else:
        # L=1 is pathological (GSPMD picks different strategies for the
        # degenerate depth — observed +43% FLOPs); 2→3 deltas are clean.
        l1, l2 = 2, 3
        c1 = dataclasses.replace(cfg, num_layers=l1)
        c2 = dataclasses.replace(cfg, num_layers=l2)
    return c1, c2, l1, l2, cfg.num_layers


def _measure(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
             causal_skip: bool, scheme: str = "tp",
             attn_flip: bool = False,
             remat: bool = True) -> tuple[float, float,
                                          HA.CollectiveStats]:
    from repro.launch import dryrun as DR
    with SET.analysis_mode():
        lowered = DR.build_lowered(cfg, shape, mesh,
                                   causal_skip=causal_skip, donate=False,
                                   scheme=scheme, attn_flip=attn_flip,
                                   remat=remat)
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    coll = HA.parse_collectives(compiled.as_text())
    return flops, byts, coll


def roofline_cell(cfg: ModelConfig, shape: ShapeSpec, mesh, chips: int, *,
                  causal_skip: bool = True, scheme: str = "tp",
                  attn_flip: bool = False,
                  remat: bool = True) -> HA.Roofline:
    from repro.launch.dryrun import model_flops
    c1, c2, l1, l2, lt = analysis_depths(cfg)
    kw = dict(causal_skip=causal_skip, scheme=scheme, attn_flip=attn_flip,
              remat=remat)
    f1, b1, coll1 = _measure(c1, shape, mesh, **kw)
    f2, b2, coll2 = _measure(c2, shape, mesh, **kw)
    r = (lt - l1) / (l2 - l1)
    flops = f1 + r * (f2 - f1)
    byts = b1 + r * (b2 - b1)
    coll = coll1.plus(coll2.minus(coll1).scaled(r))

    pk = M.peaks()
    compute_s = flops / pk["flops_bf16"]
    memory_s = byts / pk["hbm_bw"]
    collective_s = coll.total_bytes / pk["ici_bw_per_link"]
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape)
    useful = mf / (flops * chips) if flops else 0.0
    return HA.Roofline(
        flops=flops, bytes_accessed=byts, collective_bytes=coll.total_bytes,
        chips=chips, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant, model_flops=mf,
        useful_ratio=useful, collectives=coll, per_device_mem=0)
