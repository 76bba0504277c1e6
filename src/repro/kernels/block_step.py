"""Store-resident event-block megakernel (DESIGN.md §10).

One ``pl.pallas_call`` advances the engine through a BLOCK of
``W = cfg.block_events`` events: the PM store, window ring, overload
scalars and per-pattern counters stay resident (VMEM on TPU) for the
whole block while an in-kernel loop replays the paper's per-event
operator — expire → Algorithm-1 overload check → E-BL drop → advance →
complete → spawn → observation gather → simulated time — and writes one
``StepOut`` row per event into output tiles.  The per-event jnp step
streams the whole (P, N) store through HBM several times per event; here
it is loaded once per W events.

Shedding protocol — FUSED (the default, ``cfg.block_shed="fused"``):
a ``shed ∧ ρ>0`` event is handled INSIDE the loop.  Under a
``lax.cond`` (so unfired events pay nothing) the kernel recomputes the
store-resident utility column (pSPICE: the interpolated table lookup of
``core.utility.multi_pattern_lookup``, arithmetic-identical; PM-BL: a
host-precomputed row of iid uniforms) and applies the same O(N)
histogram-refinement select the host paths use
(``core.shedder.threshold_drop_mask_rows``, bitwise
``threshold_drop_mask``), then pays the shed cost, bumps
pms_shed/shed_calls and continues to the normal advance/spawn path of
the SAME event.  PRNG discipline survives fusion because the wrapper
precomputes the whole per-fire key chain host-side (``keys[t+1],
subs[t] = split(keys[t])``): the kernel only counts fires, the wrapper
advances ``carry.key`` to ``chain[n_fires]``, and PM-BL's uniforms are
drawn from exactly the ``sub`` the host path would have used for the
same fire ordinal.  A block with F fires is still ONE launch.

Legacy protocol (block split, ``cfg.block_shed="replay"`` — kept as the
oracle, and the forced path for ``shed_plan="sort"``): the loop stops
committing at the first ``shed ∧ ρ>0`` event and reports ``(fired,
fire_idx)``; the engine driver replays that event through the ordinary
``_step`` and re-enters the kernel at ``fire_idx + 1``.

Either way every committed quantity goes through arithmetic
bit-identical to the xla backend's (same operation order; lookups and
slot picks are one-hot selects with exactly one hit), which is what lets
tests/test_block_backend.py and the eval/oracle.py suite demand EXACT
equality.

The body is written in what the TPU kernel compiler (Mosaic) lowers, and
the same body runs interpreted off-TPU: per-event scalars live in SMEM;
the resident store is 2-D (P, N) int32 (masks are compared from int32,
never carried as bool); the ANY distinctness sets enter as A planes of
(P, N); table lookups and slot allocation are one-hot selects and lane
minima instead of gathers, scatters, ``cumsum`` or ``argmax``; the
latency-sample ring is a (rows, 128) tile updated one row per event.
``tests/test_tpu_compile.py`` compiles every variant for a TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.cep import patterns as pat
from repro.core import overload as ovl
from repro.core import shedder as shd
from repro.kernels.tiling import LANES   # the lat ring is (rows, LANES)

SHED_PSPICE, SHED_PMBL, SHED_EBL = "pspice", "pmbl", "ebl"

SHED_NBINS = 128   # the engine shed paths' histogram width (kops default)

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
_VMEM = pl.BlockSpec(memory_space=pltpu.VMEM)


def fused_shed(cfg) -> bool:
    """True when this config runs Algorithm 2 inside the block kernel.

    The fused path implements the O(N) threshold plan only; the sort
    plan (the argsort oracle) and an explicit ``block_shed="replay"``
    pin the legacy block-split protocol instead."""
    return (cfg.shedder in (SHED_PSPICE, SHED_PMBL)
            and cfg.shed_plan == "threshold"
            and getattr(cfg, "block_shed", "fused") == "fused")


def _pspice_utilities(tab_ref, bins, state, r_w, B, M):
    """``core.utility.multi_pattern_lookup`` over the (P, N) store.

    Same arithmetic; the two table reads are one-hot selects over the
    (B, P, M) table (exactly one (bin, state) hit per slot)."""
    f32, i32 = jnp.float32, jnp.int32
    pos = jnp.clip(r_w.astype(f32) / bins - 1.0, 0.0, B - 1.0)
    b0 = jnp.floor(pos).astype(i32)
    b1 = jnp.minimum(b0 + 1, B - 1)
    frac = pos - b0.astype(f32)
    s_hit = [state == m for m in range(M)]

    def per_bin(b, u01):
        u0, u1 = u01
        tab = tab_ref[b]                                   # (P, M)
        for m in range(M):
            val = tab[:, m:m + 1]
            u0 = jnp.where((b0 == b) & s_hit[m], val, u0)
            u1 = jnp.where((b1 == b) & s_hit[m], val, u1)
        return u0, u1

    zero = jnp.zeros(state.shape, f32)
    u0, u1 = jax.lax.fori_loop(0, B, per_bin, (zero, zero))
    return u0 * (1.0 - frac) + u1 * frac


def _block_kernel(*refs, spec, in_names, out_names):
    """Kernel body: the resident state is copied into the output buffers
    once, every event reads and rewrites it there, and the scalar state
    rides the loop carry."""
    (P, N, M, A, K, S, W) = (spec["P"], spec["N"], spec["M"], spec["A"],
                             spec["K"], spec["S"], spec["W"])
    kinds, spawn_modes = spec["kinds"], spec["spawn_modes"]
    shedder, emit, stats = spec["shedder"], spec["emit"], spec["stats"]
    fused = spec["fused"]
    f32, i32 = jnp.float32, jnp.int32
    r = dict(zip(list(in_names) + ["o_" + n for n in out_names], refs))

    # SMEM operands are (1, n) rows: a leading unit dim keeps their last
    # two dims whole when the lane vmap adds a batch axis in front.
    sm = lambda name, k: r[name][0, k]                     # noqa: E731
    iscal = [sm("iscal", k) for k in range(6)]
    fscal = [sm("fscal", k) for k in range(13)]
    s0, n_valid, i0, lat_ptr0 = iscal[0], iscal[1], iscal[2], iscal[3]
    f_model = ovl.LatencyModel(a=fscal[6], b=fscal[7], kind=iscal[4])
    g_model = ovl.LatencyModel(a=fscal[8], b=fscal[9], kind=iscal[5])
    ebl_raw_mean = fscal[10]
    proc = [sm("proc_cost", p) for p in range(P)]

    for name in ("active", "state", "open_idx", "bind", "ring", "ring_ptr",
                 "complex_count", "pms_created", "lat_n", "lat_l"):
        r["o_" + name][...] = r[name][...]
    if kinds != "seq":
        r["o_idset"][...] = r["idset"][...]
    if stats:
        r["o_obs_counts"][...] = r["obs_counts"][...]
        r["o_obs_rewards"][...] = r["obs_rewards"][...]

    wsz = r["window_size"][...]                         # (P, 1) i32
    final = r["final_state"][...]
    usesb = r["uses_binding"][...] != 0
    smode = r["spawn_mode"][...]
    scount = r["spawn_counts"][...]
    is_seq = r["kind"][...] == pat.KIND_SEQ
    at_open_m = smode == pat.SPAWN_AT_OPEN
    iota_n = jax.lax.broadcasted_iota(i32, (P, N), 1)
    iota_k = jax.lax.broadcasted_iota(i32, (P, K), 1)
    lane = jax.lax.broadcasted_iota(i32, (1, LANES), 1)
    if stats:
        iota_m = jax.lax.broadcasted_iota(i32, (M, 1), 0)

    def overload(l_q, n_pm_i):
        return ovl.detect_overload(f_model, g_model, l_q, n_pm_i,
                                   spec["latency_bound"],
                                   spec["safety_buffer"], lazy=True)

    def run_shed(active1, i, open_idx, state, rho, nfire):
        """In-kernel Algorithm 2 on the committed (post-expiry) store."""
        if shedder == SHED_PSPICE:
            u = _pspice_utilities(r["ut_tables"], r["ut_bins"][...], state,
                                  wsz - (i - open_idx), spec["B"], M)
        else:
            # PM-BL: row ``nfire`` of the host-drawn uniforms — exactly
            # the draw the host path makes from the (nfire+1)-th key split.
            u = r["shed_uniforms"][jnp.minimum(nfire, W - 1)]
        kept = shd.threshold_drop_mask_rows(active1, u, rho,
                                            nbins=SHED_NBINS)
        return kept.astype(i32)

    def commit_event(j, c, fire, rho, expired, n_act1, sim1, l_q, n_pm_i):
        """Steps 2b-7 for event j; writes the resident state and row j."""
        (_, _, ema, prev, eblf, ovf, ebld, pshed, scalls, lat_ptr,
         nfire, fire_idx) = c
        i = i0 + j
        n_pm_f = n_pm_i.astype(f32)
        arr = sm("arrival", j)
        ec, eb = r["ev_class"][j], r["ev_bind"][j]        # (P, 1)
        eo = r["ev_open"][j] != 0
        eid = sm("ev_id", j)
        active = r["o_active"][...] != 0
        state, open_idx = r["o_state"][...], r["o_open_idx"][...]
        bind = r["o_bind"][...]
        active1 = active & ~expired

        if spawn_modes != "at_open":
            ring, ring_ptr = r["o_ring"][...], r["o_ring_ptr"][...]
            opens = eo & (smode == pat.SPAWN_IN_WINDOWS)
            ring = jnp.where(opens & (iota_k == ring_ptr), i, ring)
            r["o_ring"][...] = ring
            r["o_ring_ptr"][...] = jnp.where(opens, (ring_ptr + 1) % K,
                                             ring_ptr)

        # -- 2b. in-kernel Algorithm 2 (fused shed path) ------------------
        if fused:
            kept = jax.lax.cond(
                fire,
                lambda: run_shed(active1, i, open_idx, state, rho, nfire),
                lambda: active1.astype(i32))
            active1 = kept != 0
            n_act1 = jnp.where(fire, jnp.sum(kept, axis=1, keepdims=True),
                               n_act1)
            pshed = jnp.where(
                fire, pshed + (n_pm_i - jnp.sum(kept)).astype(f32), pshed)
            scalls = jnp.where(fire, scalls + 1.0, scalls)
            sim1 = jnp.where(
                fire, sim1 + (spec["c_shed_base"] +
                              spec["c_shed_pm"] * n_pm_f), sim1)
            nfire = nfire + fire.astype(i32)

        # -- 3. E-BL drop + inter-arrival EMA ----------------------------
        gap = jnp.maximum(arr - prev, 1e-9)
        ema = 0.99 * ema + 0.01 * gap
        prev = arr
        dropped_e = jnp.bool_(False)
        did_shed_row = fire
        if shedder == SHED_EBL:
            dec_e = overload(l_q, n_pm_i)
            l_p_est = ovl.predict_latency(f_model, n_pm_f)
            d_ff = (l_p_est - ema) / jnp.maximum(
                l_p_est - spec["c_ebl"], 1e-9)
            d_bk = spec["ebl_backlog_gain"] * l_q / spec["latency_bound"]
            d_need = jnp.clip(d_ff + d_bk, 0.0, 1.0)
            eblf = jnp.where(dec_e.shed,
                             jnp.maximum(eblf * spec["ebl_decay"], d_need),
                             eblf * spec["ebl_decay"])
            raw_eff = (spec["ebl_floor"] +
                       (1.0 - spec["ebl_floor"]) * sm("ebl_raw", j))
            mean_eff = (spec["ebl_floor"] +
                        (1.0 - spec["ebl_floor"]) * ebl_raw_mean)
            p_drop = jnp.clip(raw_eff * eblf / jnp.maximum(mean_eff, 1e-9),
                              0.0, 1.0)
            dropped_e = sm("ev_rand", j) < p_drop
            ebld = jnp.where(dropped_e, ebld + 1.0, ebld)
            did_shed_row = dec_e.shed
        lc = jnp.where(dropped_e, 0, ec)                    # live class
        lo = eo & ~dropped_e                                # live open

        # -- 4. advance + completions ------------------------------------
        bind_ok = ~usesb | (bind == eb)
        if kinds != "any":
            tcol = r["tcols"][j]                             # (P, M) i32
            looked = jnp.zeros((P, N), i32)
            for m in range(M):
                looked = jnp.where(state == m, tcol[:, m:m + 1], looked)
            seq_next = jnp.where(bind_ok & ~dropped_e, looked, state)
        if kinds != "seq":
            ids = [r["o_idset"][a] for a in range(A)]        # A × (P, N)
            in_set = ids[0] == eid
            for a in range(1, A):
                in_set = in_set | (ids[a] == eid)
            any_match = bind_ok & (lc == 1) & ~in_set & (state < final)
            any_next = state + any_match.astype(i32)
            slot_ins = jnp.clip(state - 1 + scount, 0, A - 1)
            do_ins = ~is_seq & active1 & any_match
            ids = [jnp.where(do_ins & (slot_ins == a), eid, ids[a])
                   for a in range(A)]
        if kinds == "seq":
            nxt_state = seq_next
        elif kinds == "any":
            nxt_state = any_next
        else:
            nxt_state = jnp.where(is_seq, seq_next, any_next)
        new_state = jnp.where(active1, nxt_state, state)
        completed = active1 & (nxt_state == final) & (state != final)
        ncomp = jnp.sum(completed.astype(i32), axis=1, keepdims=True)
        active2 = active1 & ~completed
        n_act2 = n_act1 - ncomp
        r["o_complex_count"][...] += ncomp.astype(f32)
        if emit:
            r["o_m_open"][j] = jnp.where(completed, open_idx, -1)
            r["o_m_bind"][j] = jnp.where(completed, bind, -1)

        # -- 6. observations (model-building phase only) ------------------
        # Exact per-event transition counts (one-hot product of old and
        # new states); rewards add count × the pattern's per-PM time.
        if stats:
            for p in range(P):
                oh_s = ((state[p:p + 1, :] == iota_m) &
                        active1[p:p + 1, :]).astype(f32)     # (M, N)
                oh_n = (new_state[p:p + 1, :] == iota_m).astype(f32)
                delta = jax.lax.dot_general(
                    oh_s, oh_n, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32)              # (M, M)
                r["o_obs_counts"][p] = r["o_obs_counts"][p] + delta
                r["o_obs_rewards"][p] = (r["o_obs_rewards"][p] +
                                         delta * (spec["c_match"] * proc[p]))

        # -- 5. spawn: candidate k takes the lowest free slot left -------
        free = ~active2
        active3, state3, open3, bind3 = active2, new_state, open_idx, bind
        spawned = jnp.zeros((P, 1), i32)
        if kinds != "seq":
            row0 = jnp.where(scount > 0, eid, -1)            # (P, 1)
        if spawn_modes == "at_open":
            cands = [(lo, i)]
        else:
            ring = r["o_ring"][...]
            cands = []
            for k in range(K):
                ring_k = ring[:, k:k + 1]
                exists = jnp.max(
                    (active2 & (open_idx == ring_k) & (bind == eb)
                     ).astype(i32), axis=1, keepdims=True) > 0
                win = ((ring_k >= 0) & ((i - ring_k) < wsz) & ~exists &
                       (lc == 1) & ~at_open_m)
                if spawn_modes == "in_windows":
                    cands.append((win, ring_k))
                elif k == 0:
                    cands.append((win | (at_open_m & lo),
                                  jnp.where(at_open_m, i, ring_k)))
                else:
                    cands.append((win, ring_k))
        for cand, cand_open in cands:
            slot = jnp.min(jnp.where(free, iota_n, N), axis=1, keepdims=True)
            can = cand & (slot < N)
            sel = can & (iota_n == slot)
            free = free & ~sel
            active3 = active3 | sel
            state3 = jnp.where(sel, 1, state3)
            open3 = jnp.where(sel, cand_open, open3)
            bind3 = jnp.where(sel, eb, bind3)
            if kinds != "seq":
                ids = [jnp.where(sel, row0 if a == 0 else -1, ids[a])
                       for a in range(A)]
            ovf = ovf + jnp.sum((cand & ~can).astype(i32)).astype(f32)
            spawned = spawned + can.astype(i32)
        r["o_active"][...] = active3.astype(i32)
        r["o_state"][...] = state3
        r["o_open_idx"][...] = open3
        r["o_bind"][...] = bind3
        if kinds != "seq":
            for a in range(A):
                r["o_idset"][a] = ids[a]
        r["o_pms_created"][...] += spawned.astype(f32)
        n_act3 = n_act2 + spawned

        # -- 7. simulated processing time & latency ----------------------
        # The per-pattern sum runs in pattern order, as the xla step's.
        t_proc = None
        for p in range(P):
            term = (spec["c_match"] * proc[p]) * n_act1[p, 0].astype(f32)
            t_proc = term if t_proc is None else t_proc + term
        t_proc = spec["c_base"] + t_proc
        t_proc = jnp.where(dropped_e, spec["c_ebl"], t_proc)
        sim = sim1 + t_proc
        ptr = lat_ptr % S
        row, col = ptr // LANES, ptr % LANES
        for name, val in (("o_lat_n", n_pm_f), ("o_lat_l", t_proc)):
            old = r[name][pl.ds(row, 1), :]
            r[name][pl.ds(row, 1), :] = jnp.where(lane == col, val, old)
        r["o_l_e"][0, j] = sim - arr
        r["o_n_pm"][0, j] = jnp.sum(n_act3).astype(f32)
        r["o_shed"][0, j] = did_shed_row.astype(i32)
        r["o_dropped"][0, j] = dropped_e.astype(i32)
        return (n_act3, sim, ema, prev, eblf, ovf, ebld, pshed, scalls,
                lat_ptr + 1, nfire, fire_idx)

    def body(st):
        j, c = st
        n_act, sim = c[0], c[1]
        i = i0 + j
        arr = sm("arrival", j)
        active = r["o_active"][...] != 0
        expired = active & ((i - r["o_open_idx"][...]) >= wsz)
        n_act1 = n_act - jnp.sum(expired.astype(i32), axis=1, keepdims=True)
        sim1 = jnp.maximum(sim, arr)
        l_q = sim1 - arr
        n_pm_i = jnp.sum(n_act1)
        fire, rho = jnp.bool_(False), jnp.int32(0)
        if shedder in (SHED_PSPICE, SHED_PMBL):
            dec = overload(l_q, n_pm_i)
            fire, rho = dec.shed & (dec.rho > 0), dec.rho
        args = (j, c, fire, rho, expired, n_act1, sim1, l_q, n_pm_i)
        if fused or shedder not in (SHED_PSPICE, SHED_PMBL):
            return j + 1, commit_event(*args)
        # Replay: the fire event is NOT committed — the driver replays it
        # through the host ``_step``; the loop stops here.
        stop = c[:10] + (c[10] + 1, j)
        return j + 1, jax.lax.cond(fire, lambda: stop,
                                   lambda: commit_event(*args))

    active0 = r["active"][...]
    carry0 = (jnp.sum(active0, axis=1, keepdims=True),
              fscal[0], fscal[2], fscal[3], fscal[1], fscal[4], fscal[5],
              fscal[11], fscal[12], lat_ptr0, jnp.int32(0), jnp.int32(W))
    # Event loop over [s, n_valid).  Fused mode runs the whole span in
    # one pass (``nfire`` counts in-kernel fires for the wrapper's
    # key-chain advance).  Replay mode stops after the first Algorithm-1
    # fire; rows outside the committed range stay unwritten and the
    # driver only reads [s, stop).
    if fused:
        loop_cond = lambda st: st[0] < n_valid               # noqa: E731
    else:
        loop_cond = lambda st: ((st[0] < n_valid) &          # noqa: E731
                                (st[1][10] == 0))
    out = jax.lax.while_loop(loop_cond, body, (s0, carry0))[1]
    (_, sim, ema, prev, eblf, ovf, ebld, pshed, scalls, lat_ptr, nfire,
     fire_idx) = out
    for k, v in enumerate((sim, eblf, ema, prev, ovf, ebld, pshed, scalls)):
        r["o_fscal_out"][0, k] = v
    for k, v in enumerate((nfire, fire_idx, lat_ptr)):
        r["o_iscal_out"][0, k] = v


def block_step(cfg, model, carry, blk, i0, s, n_valid, *, interpret: bool):
    """Run the fused block step: ``W = cfg.block_events`` events against
    the resident carry, starting at in-block offset ``s`` (events before
    ``s`` were committed by a previous entry — the block-split protocol),
    masking events at ``>= n_valid`` (ragged tail blocks).

    ``cfg`` / ``model`` / ``carry`` / ``blk`` are the engine's
    ``EngineConfig`` / ``EngineModel`` / ``Carry`` / block-shaped
    ``EventBatch`` (duck-typed; this module never imports the engine).
    ``interpret`` runs the kernel body through the Pallas interpreter
    (no TPU); the engine passes ``kernels.ops.default_interpret()``.
    Returns ``(carry', rows, fired, fire_idx)`` where ``rows`` is a dict
    of per-event StepOut columns.

    Under the FUSED shed plan (``fused_shed(cfg)``) Algorithm-2 fires
    are handled in-kernel: ``fired`` is always False, rows are valid on
    all of ``[s, n_valid)``, and ``carry'`` — including ``key`` (advanced
    down the precomputed split chain once per fire), ``pms_shed`` and
    ``shed_calls`` — has every valid event committed.  Under the legacy
    replay plan rows are valid on ``[s, stop)`` with ``stop = fire_idx
    if fired else n_valid`` and the fired event is left to the driver.
    Either way every committed event is bit-identical to the xla step.
    """
    P, N, M = cfg.num_patterns, cfg.max_pms, cfg.max_states
    A, K, W = cfg.max_any_ids, cfg.ring_size, cfg.block_events
    S = carry.lat_samples_n.shape[0]
    rows_s = -(-S // LANES)
    i32, f32 = jnp.int32, jnp.float32
    fused = fused_shed(cfg)
    spec = dict(P=P, N=N, M=M, A=A, K=K, S=S, W=W,
                B=model.ut_tables.shape[1], fused=fused,
                kinds=cfg.kinds, spawn_modes=cfg.spawn_modes,
                shedder=cfg.shedder, emit=cfg.emit_matches,
                stats=cfg.gather_stats,
                c_base=cfg.c_base, c_match=cfg.c_match, c_ebl=cfg.c_ebl,
                c_shed_base=cfg.c_shed_base, c_shed_pm=cfg.c_shed_pm,
                latency_bound=cfg.latency_bound,
                safety_buffer=cfg.safety_buffer,
                ebl_backlog_gain=cfg.ebl_backlog_gain,
                ebl_decay=cfg.ebl_decay, ebl_floor=cfg.ebl_floor)

    # Per-event SEQ transition columns, gathered OUTSIDE the kernel
    # (tiny: (W, P, M)); class 0 self-loops cover bind-fail / E-BL drop.
    tt = jnp.transpose(model.trans, (0, 2, 1))               # (P, C+1, M)
    tcols = tt[jnp.arange(P, dtype=i32)[None, :], blk.ev_class]
    pms = carry.pms
    iscal = jnp.stack([jnp.asarray(s, i32), jnp.asarray(n_valid, i32),
                       jnp.asarray(i0, i32), carry.lat_ptr,
                       model.f_model.kind, model.g_model.kind])
    fscal = jnp.stack([carry.sim_time, carry.ebl_frac, carry.ema_gap,
                       carry.prev_arrival, carry.overflow,
                       carry.ebl_dropped, model.f_model.a, model.f_model.b,
                       model.g_model.a, model.g_model.b,
                       model.ebl_raw_mean, carry.pms_shed,
                       carry.shed_calls])
    col = lambda x: x.astype(i32)[:, None]                   # noqa: E731
    row = lambda x: x[None, :]                               # noqa: E731
    ev_col = lambda x: x.astype(i32)[..., None]              # noqa: E731
    lat = lambda x: jnp.pad(x, (0, rows_s * LANES - S)).reshape(  # noqa
        rows_s, LANES)

    # PRNG discipline under fusion: the host path splits the carry key
    # once per fire (``key, sub = split(key)``; only PM-BL consumes
    # ``sub``).  Precompute the whole chain for the worst case of W fires
    # — the kernel merely COUNTS fires and the wrapper advances the carry
    # key to ``chain[n_fires]``, so F in-kernel fires leave exactly the
    # key F host fires would have.  Unused tail splits are pure compute.
    if fused:
        def _split(k, _):
            nk, sub = jax.random.split(k)
            return nk, (nk, sub)
        _, (chain_keys, chain_subs) = jax.lax.scan(
            _split, carry.key, None, length=W)
        key_chain = jnp.concatenate([carry.key[None], chain_keys], axis=0)
    # Named operands with their memory space; the kernel looks refs up by
    # name, and the in-place alias map is derived by name below.
    inputs = [("tcols", tcols, _VMEM), ("ev_class", ev_col(blk.ev_class),
                                        _VMEM),
              ("ev_bind", ev_col(blk.ev_bind), _VMEM),
              ("ev_open", ev_col(blk.ev_open), _VMEM),
              ("ev_id", row(blk.ev_id), _SMEM),
              ("ev_rand", row(blk.ev_rand), _SMEM),
              ("ebl_raw", row(blk.ebl_raw), _SMEM),
              ("arrival", row(blk.arrival), _SMEM),
              ("iscal", row(iscal), _SMEM), ("fscal", row(fscal), _SMEM),
              ("active", pms.active.astype(i32), _VMEM),
              ("state", pms.state, _VMEM),
              ("open_idx", pms.open_idx, _VMEM), ("bind", pms.bind, _VMEM),
              ("ring", carry.ring, _VMEM),
              ("ring_ptr", col(carry.ring_ptr), _VMEM),
              ("window_size", col(model.window_size), _VMEM),
              ("final_state", col(model.final_state), _VMEM),
              ("uses_binding", col(model.uses_binding), _VMEM),
              ("kind", col(model.kind), _VMEM),
              ("spawn_mode", col(model.spawn_mode), _VMEM),
              ("spawn_counts", col(model.spawn_counts), _VMEM),
              ("proc_cost", row(model.proc_cost.astype(f32)), _SMEM),
              ("complex_count", carry.complex_count[:, None], _VMEM),
              ("pms_created", carry.pms_created[:, None], _VMEM),
              ("lat_n", lat(carry.lat_samples_n), _VMEM),
              ("lat_l", lat(carry.lat_samples_l), _VMEM)]
    if cfg.kinds != "seq":
        inputs += [("idset", jnp.transpose(pms.idset, (2, 0, 1)), _VMEM)]
    if fused and cfg.shedder == SHED_PSPICE:
        inputs += [("ut_tables",
                    jnp.transpose(model.ut_tables.astype(f32), (1, 0, 2)),
                    _VMEM),
                   ("ut_bins", model.ut_bins.astype(f32)[:, None], _VMEM)]
    if fused and cfg.shedder == SHED_PMBL:
        # One iid-uniform score row per potential fire, drawn from the
        # chain's subs — bitwise the ``random_drop`` draw the host path
        # makes for the same fire ordinal.
        unif = jax.vmap(
            lambda kk: jax.random.uniform(kk, (P * N,)))(chain_subs)
        inputs += [("shed_uniforms", unif.reshape(W, P, N), _VMEM)]
    if cfg.gather_stats:
        inputs += [("obs_counts", carry.obs_counts, _VMEM),
                   ("obs_rewards", carry.obs_rewards, _VMEM)]

    sds = jax.ShapeDtypeStruct
    outputs = [("active", sds((P, N), i32), _VMEM),
               ("state", sds((P, N), i32), _VMEM),
               ("open_idx", sds((P, N), i32), _VMEM),
               ("bind", sds((P, N), i32), _VMEM),
               ("ring", sds((P, K), i32), _VMEM),
               ("ring_ptr", sds((P, 1), i32), _VMEM),
               ("complex_count", sds((P, 1), f32), _VMEM),
               ("pms_created", sds((P, 1), f32), _VMEM),
               ("lat_n", sds((rows_s, LANES), f32), _VMEM),
               ("lat_l", sds((rows_s, LANES), f32), _VMEM),
               ("fscal_out", sds((1, 8), f32), _SMEM),
               ("iscal_out", sds((1, 3), i32), _SMEM),
               ("l_e", sds((1, W), f32), _SMEM),
               ("n_pm", sds((1, W), f32), _SMEM),
               ("shed", sds((1, W), i32), _SMEM),
               ("dropped", sds((1, W), i32), _SMEM)]
    if cfg.kinds != "seq":
        outputs += [("idset", sds((A, P, N), i32), _VMEM)]
    if cfg.emit_matches:
        outputs += [("m_open", sds((W, P, N), i32), _VMEM),
                    ("m_bind", sds((W, P, N), i32), _VMEM)]
    if cfg.gather_stats:
        outputs += [("obs_counts", sds((P, M, M), f32), _VMEM),
                    ("obs_rewards", sds((P, M, M), f32), _VMEM)]
    in_names = [name for name, _, _ in inputs]
    out_names = [name for name, _, _ in outputs]
    aliases = {in_names.index(name): k for k, name in enumerate(out_names)
               if name in in_names}

    out = pl.pallas_call(
        functools.partial(_block_kernel, spec=spec, in_names=in_names,
                          out_names=out_names),
        out_shape=[shape for _, shape, _ in outputs],
        in_specs=[space for _, _, space in inputs],
        out_specs=[space for _, _, space in outputs],
        input_output_aliases=aliases,
        interpret=interpret,
        name="cep_block_step",
    )(*[arr for _, arr, _ in inputs])
    o = dict(zip(out_names, out))

    m_open = o.get("m_open", jnp.zeros((W, P, 0), i32))
    m_bind = o.get("m_bind", jnp.zeros((W, P, 0), i32))
    idset = (jnp.transpose(o["idset"], (1, 2, 0)) if cfg.kinds != "seq"
             else pms.idset)
    fscal_o, iscal_o = o["fscal_out"][0], o["iscal_out"][0]
    unlat = lambda x: x.reshape(-1)[:S]                      # noqa: E731
    carry2 = carry._replace(
        pms=pms._replace(active=o["active"] != 0, state=o["state"],
                         open_idx=o["open_idx"], bind=o["bind"],
                         idset=idset),
        ring=o["ring"], ring_ptr=o["ring_ptr"][:, 0],
        sim_time=fscal_o[0], ebl_frac=fscal_o[1], ema_gap=fscal_o[2],
        prev_arrival=fscal_o[3], overflow=fscal_o[4],
        ebl_dropped=fscal_o[5],
        pms_shed=fscal_o[6], shed_calls=fscal_o[7],
        complex_count=o["complex_count"][:, 0],
        pms_created=o["pms_created"][:, 0],
        obs_counts=o.get("obs_counts", carry.obs_counts),
        obs_rewards=o.get("obs_rewards", carry.obs_rewards),
        lat_samples_n=unlat(o["lat_n"]), lat_samples_l=unlat(o["lat_l"]),
        lat_ptr=iscal_o[2])
    rows = dict(l_e=o["l_e"][0], n_pm=o["n_pm"][0], shed=o["shed"][0] != 0,
                dropped=o["dropped"][0] != 0, match_open=m_open,
                match_bind=m_bind)
    if fused:
        # iscal_o[0] counts in-kernel fires: advance the key down the
        # precomputed chain and report "nothing left to replay".
        carry2 = carry2._replace(key=jax.lax.dynamic_index_in_dim(
            key_chain, iscal_o[0], axis=0, keepdims=False))
        return carry2, rows, jnp.bool_(False), iscal_o[1]
    return carry2, rows, iscal_o[0] != 0, iscal_o[1]
