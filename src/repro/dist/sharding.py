"""Sharding rules for the production meshes (DESIGN.md §5).

One module owns every PartitionSpec in the system:

  param_specs(mesh, cfg, params, scheme)  — per-architecture parameter
      layouts: head-sharded attention (with divisibility fallback to
      replicated), FSDP ("data","model") MLP/embed sharding, MoE experts
      on the model axis, Mamba channel sharding.
  batch_axes(mesh, global_batch)          — which mesh axes the batch dim
      spreads over, flattening multi-pod meshes to ("pod", "data") and
      dropping leading axes until the batch divides.
  batch_specs(mesh, cfg, batch, scheme)   — specs for train/prefill input
      structs (tokens / labels / patches / frames).
  cache_specs(mesh, cfg, cache)           — decode KV-cache layout: batch
      over the data axes, cache *sequence* over "model" (the memory-
      critical decode layout, DESIGN.md §6).
  pm_specs(mesh, engine_cfg)              — CEP-side: partitions the
      (P, N) partial-match store of the vectorized pSPICE operator across
      the data axis (pattern-parallel).
  run_engine_sharded(...)                 — shard_map over run_engine
      using pm_specs, so multi-query workloads scale past one device.
  lane_specs / run_chunk_lanes_sharded    — the runtime's tenant lanes
      (repro.runtime, DESIGN.md §7): lane axis over "data", per-lane
      pattern axis over "model", so lanes × patterns cover a 2-D mesh;
      lane_refresh_programs places the lanes' model refresh the same way.

Every rule goes through `_fit`, which drops any axis assignment that does
not divide the dimension — specs are correct by construction on any mesh.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import (AbstractMesh, AxisType, NamedSharding,
                          PartitionSpec as P)

from repro.models.config import ModelConfig

PyTree = Any


# Meshes here use Auto axes: jax meshes default to Explicit axes, under
# which the specs below and ``with_sharding_constraint`` do not apply.

def make_mesh(shape, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axes."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names), **kw)


def abstract_mesh(axis_sizes, axis_names) -> AbstractMesh:
    """Device-free mesh with Auto axes, for spec construction."""
    return AbstractMesh(tuple(int(s) for s in axis_sizes), tuple(axis_names),
                        axis_types=(AxisType.Auto,) * len(axis_names))


# ---------------------------------------------------------------------------
# Spec plumbing
# ---------------------------------------------------------------------------

def _axis_size(mesh, axes) -> int:
    shape = dict(mesh.shape)
    size = 1
    for a in axes:
        size *= shape[a]
    return size


def _norm(axes):
    """Normalize an axis group to a PartitionSpec entry."""
    if not axes:
        return None
    if len(axes) == 1:
        return axes[0]
    return tuple(axes)


def _fit(mesh, shape, entries) -> P:
    """PartitionSpec from per-dim axis proposals, dropping (from the left)
    any axes absent from the mesh or not dividing the dim."""
    names = set(mesh.axis_names)
    out = []
    for dim, ax in zip(shape, entries):
        if ax is None:
            out.append(None)
            continue
        ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
        ax_t = tuple(a for a in ax_t if a in names)
        while ax_t and dim % _axis_size(mesh, ax_t) != 0:
            ax_t = ax_t[1:]
        out.append(_norm(ax_t))
    return P(*out)


def spec(mesh, shape, *entries) -> P:
    """Public ad-hoc spec builder with the same divisibility fallback."""
    return _fit(mesh, shape, entries)


def named_tree(mesh, tree):
    """Map a PartitionSpec pytree to NamedShardings on `mesh` (what
    jax.jit's in_shardings/out_shardings want on every jax version)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

_BLOCKS = ("attn", "mlp", "moe", "mamba")


def _leaf_spec(mesh, cfg: ModelConfig, scheme: str, block: str | None,
               name: str, shape) -> P:
    """Sharding rule for one parameter leaf.

    Axis indices are negative so the same rule covers stacked (leading L
    axis) and unstacked (shared_attn) leaves.  scheme:
      "tp"    — tensor parallelism over "model" (+FSDP over "data" when
                cfg.fsdp), the default.
      "fsdp"  — no tensor axis; params shard over ("data", "model") as one
                flat FSDP axis group.
      "moe2d" — tp + experts sharded (E × d_ff) two-dimensionally.
    """
    nd = len(shape)
    fsdp = cfg.fsdp or scheme == "fsdp"
    dp = ("data", "model") if scheme == "fsdp" else ("data",)
    tp = None if scheme == "fsdp" else "model"
    ax: dict[int, Any] = {}
    if block == "attn":
        head_tp = tp if cfg.attn_head_tp else None
        if name in ("wq", "bq", "wq_b", "wk", "wv", "bk", "bv",
                    "wk_b", "wv_b"):
            ax[-2] = head_tp
            if fsdp and nd >= 3 and not name.startswith("b"):
                ax[-3] = dp                 # d (or lora rank) over data
        elif name == "wo":
            ax[-3] = head_tp
            if fsdp:
                ax[-1] = dp
        elif name in ("wq_a", "wkv_a"):
            if fsdp:
                ax[-2] = dp
    elif block == "mlp":
        if name in ("wi", "wg"):
            ax[-1] = tp
            if fsdp:
                ax[-2] = dp
        elif name == "wo":
            ax[-2] = tp
            if fsdp:
                ax[-1] = dp
    elif block == "moe":
        if name == "router":
            ax[-1] = tp
        elif name in ("wi", "wg"):
            ax[-3] = "model"                # experts on the model axis
            if scheme == "moe2d":
                ax[-1] = "data"             # (E × d_ff) 2-D expert shard
        elif name == "wo":
            ax[-3] = "model"
            if scheme == "moe2d":
                ax[-2] = "data"
    elif block == "mamba":
        if name in ("wz", "wx"):
            ax[-1] = tp                     # channel (d_inner) sharding
            if fsdp:
                ax[-2] = dp
        elif name == "wo":
            ax[-2] = tp
            if fsdp:
                ax[-1] = dp
        elif name == "wdt":
            ax[-1] = tp                     # SSD heads are channel groups
    else:
        if name == "embed":
            ax[-2] = tp if tp else ("data", "model")
            if fsdp and tp:
                ax[-1] = "data"
        elif name == "lm_head":
            ax[-1] = tp if tp else ("data", "model")
            if fsdp and tp:
                ax[-2] = "data"
    entries = [None] * nd
    for i, a in ax.items():
        if a is not None and -nd <= i:
            entries[i] = a
    return _fit(mesh, shape, entries)


def param_specs(mesh, cfg: ModelConfig, params: PyTree,
                scheme: str = "tp") -> PyTree:
    """PartitionSpec tree mirroring `params` (arrays or ShapeDtypeStructs).

    Per-architecture rules with divisibility fallback to replicated — e.g.
    starcoder2's 48 query heads shard 16-way while its 4 KV heads stay
    replicated, and minitron's 24 heads fall back entirely.
    """
    def walk(tree: dict, block: str | None) -> dict:
        out = {}
        for key, val in tree.items():
            if isinstance(val, dict):
                if key in _BLOCKS:
                    nb = key
                elif key == "shared" and block == "moe":
                    nb = "mlp"              # shared experts are a plain MLP
                else:
                    nb = block
                out[key] = walk(val, nb)
            else:
                out[key] = _leaf_spec(mesh, cfg, scheme, block, key,
                                      val.shape)
        return out

    return walk(params, None)


# ---------------------------------------------------------------------------
# Batch & cache specs
# ---------------------------------------------------------------------------

def batch_axes(mesh, global_batch: int, scheme: str = "tp"):
    """Mesh axes the batch dim shards over, or None.

    Multi-pod meshes flatten to ("pod", "data"); pure-FSDP adds "model".
    Leading axes drop until the batch divides (e.g. batch 16 on a 2-pod
    (2, 16, 16) mesh keeps only ("data",))."""
    wanted = ("pod", "data", "model") if scheme == "fsdp" else ("pod", "data")
    axes = tuple(a for a in wanted if a in mesh.axis_names)
    while axes and global_batch % _axis_size(mesh, axes) != 0:
        axes = axes[1:]
    return axes or None


def batch_specs(mesh, cfg: ModelConfig, batch: dict,
                scheme: str = "tp") -> dict:
    """Specs for the train/prefill input dict (leading dim = batch)."""
    out = {}
    for key, val in batch.items():
        if key == "cache":
            out[key] = cache_specs(mesh, cfg, val)
            continue
        bax = batch_axes(mesh, val.shape[0], scheme)
        out[key] = P(_norm(bax) if bax else None,
                     *([None] * (val.ndim - 1)))
    return out


# Cache entries whose axis 2 is a (max_len) sequence axis we shard over
# "model" — the decode-memory-critical layout (DESIGN.md §6).  ck/cv hold
# encoder frames at axis 2; the divisibility fallback replicates them when
# the frame count (e.g. whisper's 1500) doesn't divide.
_CACHE_SEQ = ("k", "v", "sk", "sv", "ckv", "krope", "ck", "cv")


def cache_specs(mesh, cfg: ModelConfig, cache: dict) -> dict:
    """Decode-cache layout: (L, B, S, ...) → batch over data axes, cache
    sequence over "model"; SSD state heads over "model"."""
    out = {}
    for name, leaf in cache.items():
        nd = len(leaf.shape)
        if nd == 0:
            out[name] = P()
            continue
        entries: list = [None] * nd
        if nd >= 2:
            bax = batch_axes(mesh, leaf.shape[1])
            entries[1] = _norm(bax) if bax else None
        if name in _CACHE_SEQ and nd >= 3:
            entries[2] = "model"
        if name == "state" and nd >= 3:
            entries[2] = "model"            # SSD heads = channel groups
        out[name] = _fit(mesh, leaf.shape, entries)
    return out


# ---------------------------------------------------------------------------
# Launch-entry-point bundles (single owner of the assembly rules used by
# dryrun.py, train.py and serve.py)
# ---------------------------------------------------------------------------

def train_specs(mesh, cfg: ModelConfig, params, batch,
                scheme: str = "tp", pspecs=None):
    """(pspecs, ospecs, bspecs) for the train step: AdamW opt state
    mirrors the param specs with a replicated step counter.  Pass a
    precomputed `pspecs` to skip re-walking the parameter pytree."""
    if pspecs is None:
        pspecs = param_specs(mesh, cfg, params, scheme=scheme)
    ospecs = {"m": pspecs, "v": pspecs, "step": P()}
    bspecs = batch_specs(mesh, cfg, batch, scheme=scheme)
    return pspecs, ospecs, bspecs


def decode_specs(mesh, cfg: ModelConfig, global_batch: int):
    """(token_spec, logit_spec) for decode_step: tokens over the batch
    axes, logits (B, V) with vocab over "model"."""
    bax = batch_axes(mesh, global_batch)
    tok = _fit(mesh, (global_batch,), [bax])
    logits = _fit(mesh, (global_batch, cfg.vocab_size), [bax, "model"])
    return tok, logits


# ---------------------------------------------------------------------------
# CEP engine: pattern-parallel specs over the (P, N) PM store
# ---------------------------------------------------------------------------

def pm_specs(mesh, cfg, axis: str = "data") -> dict:
    """PartitionSpec pytrees for the pSPICE operator state.

    The dense PM store is (num_patterns, max_pms); pattern-parallelism
    shards the *pattern* axis across `axis` — each device runs the full
    event stream against its own slice of the query set, which is the
    natural scale-out for heavy multi-query traffic (eSPICE/hSPICE-style
    workloads).  Falls back to fully-replicated specs when num_patterns
    doesn't divide the axis.

    Returns {"carry", "model", "events", "out", "pattern_axis"} where the
    first four mirror Carry / EngineModel / EventBatch / StepOut.

    The specs cover every engine backend, including the event-block
    megakernel (``backend="pallas_block"``, DESIGN.md §10): its driver
    pads/blocks the event axis and slices StepOut back INSIDE the
    shard-mapped computation, so the block outputs cross the shard
    boundary with the exact per-event shapes specced here, and the
    pattern-axis entries apply to the shard-local (P/shards, N) store
    the kernel keeps resident.
    """
    from repro.cep import engine as eng
    from repro.core import overload as ovl

    divisible = (axis in mesh.axis_names
                 and cfg.num_patterns % _axis_size(mesh, (axis,)) == 0)
    pax = axis if divisible else None
    pms = eng.PMStore(active=P(pax, None), state=P(pax, None),
                      open_idx=P(pax, None), bind=P(pax, None),
                      idset=P(pax, None, None))
    carry = eng.Carry(
        pms=pms, ring=P(pax, None), ring_ptr=P(pax),
        sim_time=P(), key=P(None), ebl_frac=P(), ema_gap=P(),
        prev_arrival=P(),
        complex_count=P(pax), pms_created=P(pax), pms_shed=P(),
        shed_calls=P(), overflow=P(), ebl_dropped=P(),
        obs_counts=P(pax, None, None), obs_rewards=P(pax, None, None),
        lat_samples_n=P(None), lat_samples_l=P(None), lat_ptr=P())
    lat = ovl.LatencyModel(a=P(), b=P(), kind=P())
    model = eng.EngineModel(
        trans=P(pax, None, None), kind=P(pax), spawn_mode=P(pax),
        window_size=P(pax), slide=P(pax), final_state=P(pax),
        proc_cost=P(pax), uses_binding=P(pax), spawn_counts=P(pax),
        ut_tables=P(pax, None, None), ut_bins=P(pax),
        f_model=lat, g_model=lat, ebl_raw_mean=P())
    events = eng.EventBatch(
        ev_class=P(None, pax), ev_bind=P(None, pax), ev_open=P(None, pax),
        ev_id=P(None), ev_rand=P(None), ebl_raw=P(None), arrival=P(None))
    out = eng.StepOut(l_e=P(None), n_pm=P(None), shed=P(None),
                      dropped=P(None),
                      # match identities are pattern-local (zero-width
                      # unless cfg.emit_matches): shard with the pattern.
                      match_open=P(None, pax, None),
                      match_bind=P(None, pax, None))
    return {"carry": carry, "model": model, "events": events, "out": out,
            "pattern_axis": pax}


def _merge_pattern_shards(new_c, outs, axis: str):
    """Cross-shard telemetry merge for a pattern-sharded engine run: each
    shard is its own simulated parallel operator, so clocks take the
    slowest shard (pmax), counters aggregate (psum), and the latency ring
    pairs global PM counts with the slowest shard's per-event time.  Used
    by ``run_engine_sharded`` and, vmapped over tenant lanes, by
    ``run_chunk_lanes_sharded``."""
    from repro.cep import engine as eng

    psum = lambda x: jax.lax.psum(x, axis)              # noqa: E731
    pmax = lambda x: jax.lax.pmax(x, axis)              # noqa: E731
    new_c = new_c._replace(
        sim_time=pmax(new_c.sim_time),     # parallel shards: slowest
        key=pmax(new_c.key),               # shed-dependent; any valid
        ebl_frac=pmax(new_c.ebl_frac),     # conservative drop frac
        pms_shed=psum(new_c.pms_shed),
        shed_calls=psum(new_c.shed_calls),
        overflow=psum(new_c.overflow),
        ebl_dropped=psum(new_c.ebl_dropped),
        # latency-model samples: global PM count vs the slowest
        # shard's per-event time — the (n, l) pairs the parallel
        # operator's overload detector should fit.
        lat_samples_n=psum(new_c.lat_samples_n),
        lat_samples_l=pmax(new_c.lat_samples_l))
    outs = eng.StepOut(
        l_e=pmax(outs.l_e),
        n_pm=psum(outs.n_pm),
        shed=pmax(outs.shed.astype(jnp.int32)) > 0,
        dropped=pmax(outs.dropped.astype(jnp.int32)) > 0,
        # pattern-local: the out_spec concatenates shards on the pattern axis
        match_open=outs.match_open, match_bind=outs.match_bind)
    return new_c, outs


def run_engine_sharded(cfg, model, events, carry, mesh=None,
                       axis: str = "data"):
    """Pattern-parallel shard_map over run_engine.

    Each shard scans the whole stream against num_patterns/n_shards
    patterns as its OWN simulated operator — with more than one shard the
    semantics are a genuinely parallel deployment, not a bit-replay of
    the serial engine: per-event latency is the slowest shard's clock
    (pmax of l_e / sim_time / lat samples), overload and E-BL decisions
    are shard-local, and shed/drop counters aggregate per-shard decisions
    (psum).  Pattern-state outputs (complex_count, pms_created, n_pm) are
    exact regardless of shard count when no shedding triggers.  On a
    one-device mesh the results match the plain engine exactly.  Falls
    back to the plain engine when the pattern axis can't shard.
    """
    from repro.cep import engine as eng

    if mesh is None:
        ndev = len(jax.devices())
        mesh = make_mesh((ndev,), (axis,))
    specs = pm_specs(mesh, cfg, axis=axis)
    if specs["pattern_axis"] is None:
        return eng.run_engine(cfg, model, events, carry)
    n_shards = _axis_size(mesh, (axis,))
    local_cfg = dataclasses.replace(
        cfg, num_patterns=cfg.num_patterns // n_shards)

    def local_run(model, events, carry):
        new_c, outs = eng.run_engine(local_cfg, model, events, carry)
        return _merge_pattern_shards(new_c, outs, axis)

    mapped = jax.shard_map(
        local_run, mesh=mesh,
        in_specs=(specs["model"], specs["events"], specs["carry"]),
        out_specs=(specs["carry"], specs["out"]),
        check_vma=False)
    return mapped(model, events, carry)


# ---------------------------------------------------------------------------
# Runtime tenant lanes: lanes × patterns over the mesh (DESIGN.md §7)
# ---------------------------------------------------------------------------

def _prepend_axis(spec_tree, lane_ax):
    """Grow every PartitionSpec in a pytree by a leading lane entry."""
    return jax.tree.map(lambda s: P(lane_ax, *s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


def lane_specs(mesh, cfg, num_lanes: int, lane_axis: str = "data",
               pattern_axis: str | None = "model") -> dict:
    """Specs for lane-stacked runtime state: pm_specs with a leading lane
    dim.

    Lanes (independent tenants) shard over ``lane_axis``; within each lane
    the (P, N) PM store may additionally shard its pattern dim over
    ``pattern_axis`` — the lanes × patterns composition that covers a 2-D
    mesh.  Either axis falls back to replicated (None) when missing from
    the mesh, equal to the other, or not dividing its dim; with both
    fallen back the caller should use the plain vmapped path.

    Returns {"carry", "model", "events", "out", "lane_axis",
    "pattern_axis"}.
    """
    lax_ok = (lane_axis in mesh.axis_names
              and num_lanes % _axis_size(mesh, (lane_axis,)) == 0)
    lane_ax = lane_axis if lax_ok else None
    pax_name = pattern_axis if pattern_axis != lane_axis else None
    inner = pm_specs(mesh, cfg, axis=pax_name or "__none__")
    return {
        "carry": _prepend_axis(inner["carry"], lane_ax),
        "model": _prepend_axis(inner["model"], lane_ax),
        "events": _prepend_axis(inner["events"], lane_ax),
        "out": _prepend_axis(inner["out"], lane_ax),
        "lane_axis": lane_ax,
        "pattern_axis": inner["pattern_axis"],
    }


@lru_cache(maxsize=8)
def _default_lane_mesh(lane_axis: str):
    ndev = len(jax.devices())
    return make_mesh((ndev,), (lane_axis,))


@lru_cache(maxsize=32)
def _lanes_sharded_fn(cfg, mesh, num_lanes: int, lane_axis: str,
                      pattern_axis: str | None):
    """The shard-mapped, jitted, carry-donating lane chunk step — built
    ONCE per (cfg, mesh, lane count, axes) and cached, so the runtime's
    steady-state loop hits one compiled executable per chunk shape (no
    per-chunk retrace) and keeps the donation invariant of the non-mesh
    paths.  Returns None when neither axis can shard."""
    from repro.cep import engine as eng

    specs = lane_specs(mesh, cfg, num_lanes, lane_axis=lane_axis,
                       pattern_axis=pattern_axis)
    lane_ax, pax = specs["lane_axis"], specs["pattern_axis"]
    if lane_ax is None and pax is None:
        return None
    local_cfg = cfg if pax is None else dataclasses.replace(
        cfg, num_patterns=cfg.num_patterns // _axis_size(mesh, (pax,)))

    def local_run(model, events, carry, start):
        new_c, outs = eng._scan_events_lanes_backend(local_cfg, model,
                                                     events, carry,
                                                     start[0])
        if pax is not None:
            new_c, outs = _merge_pattern_shards(new_c, outs, pax)
        return new_c, outs

    mapped = jax.shard_map(
        local_run, mesh=mesh,
        in_specs=(specs["model"], specs["events"], specs["carry"], P(None)),
        out_specs=(specs["carry"], specs["out"]),
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(2,))


def run_chunk_lanes_sharded(cfg, model, events, carry, start, mesh=None,
                            lane_axis: str = "data",
                            pattern_axis: str | None = "model"):
    """Mesh-parallel chunk step for the multi-tenant runtime.

    shard_map over ``lane_specs``: each device block runs a lane-batched
    ``_scan_events_lanes`` over its local lanes × local pattern slice.
    Lanes are independent, so the lane axis needs no collectives; a
    sharded pattern axis gets the same per-lane telemetry merge as
    ``run_engine_sharded`` (psum counters, pmax clocks), vmapped over the
    lane dim.  The carry is donated, like the non-mesh chunk steps.
    Falls back to the plain lane-batched ``run_chunk_lanes`` when
    neither axis can shard (e.g. a one-axis mesh already consumed by
    lanes still shards — a no-axis fit does not).
    """
    from repro.runtime import lanes as LN

    num_lanes = events.ev_class.shape[0]
    if mesh is None:
        mesh = _default_lane_mesh(lane_axis)
    fn = _lanes_sharded_fn(cfg, mesh, num_lanes, lane_axis, pattern_axis)
    if fn is None:
        return LN.run_chunk_lanes(cfg, model, events, carry, start)
    return fn(model, events, carry, jnp.asarray(start, jnp.int32)[None])


def lane_refresh_programs(fit, deploy, mesh, cfg, num_lanes: int):
    """The lane refresh's two steps (``runtime.refresh.refresh_lanes``),
    each one compiled program over every lane of state that ``lane_specs``
    places on ``mesh``.

    ``fit(obs_counts, obs_rewards, lat_samples_n, lat_samples_l, lat_ptr)
    -> utility.ModelFit`` of one lane needs all of its patterns, so it is
    shard_mapped over the lane axis alone: each device fits its own lanes,
    with no collective unless the pattern axis has to be gathered first.
    ``deploy(model, (obs_counts, obs_rewards), (ut_tables, ut_bins,
    f_model), refit, new_f) -> (model, (obs_counts, obs_rewards))`` of one
    lane is elementwise; its results take ``lane_specs``' shardings, the
    placement the chunk step gives model and carry.  Nothing is donated.
    """
    from repro.core import utility as util

    full = lane_specs(mesh, cfg, num_lanes)
    whole = lane_specs(mesh, cfg, num_lanes, pattern_axis=None)
    f = jax.vmap(fit)
    lane_ax = whole["lane_axis"]
    if lane_ax is not None:
        c, m = whole["carry"], whole["model"]
        f = jax.shard_map(
            f, mesh=mesh,
            in_specs=(c.obs_counts, c.obs_rewards, c.lat_samples_n,
                      c.lat_samples_l, c.lat_ptr),
            out_specs=util.ModelFit(
                ut_tables=m.ut_tables, ut_bins=m.ut_bins, T=c.obs_counts,
                f_model=m.f_model, tables_finite=P(lane_ax),
                f_finite=P(lane_ax)),
            check_vma=False)
    obs = (full["carry"].obs_counts, full["carry"].obs_rewards)
    d = jax.jit(jax.vmap(deploy),
                out_shardings=named_tree(mesh, (full["model"], obs)))
    return jax.jit(f), d
