#!/usr/bin/env python3
"""Bring the CEP served path up on a TPU and check it end to end.

    python chip_smoke.py              one chip: the three phases below
    python chip_smoke.py --chips 4    four chips: the sharded paths only
    python chip_smoke.py --rehearse   the one-chip phases at a tiny size
                                      on any backend (kernels interpreted)
    python chip_smoke.py --phase P    only phase P (with either of the above)

One-chip phases, every one run on the ``xla`` reference and on the
production kernel (``pallas_block``, Algorithm 2 fused), which must agree
bit for bit:

  engine_parity  the stock scenario (3 x Q1 at windows 600/1200/2400,
                 500 symbols) at N=2048 PM slots, W=32 events per kernel
                 launch.  The model is built on the first 30% of the
                 stream through the gather-stats kernel; then 30,000
                 events at 1.4x the built max rate run for each shedder
                 (none, ebl, pspice, pmbl) with match emission: match
                 identities, counters, the l_e trace and the whole carry
                 must be equal, and pspice/pmbl must fire Algorithm 2.
                 pSPICE runs once more on an n*log2(n+1) latency model.
  oracle         the NumPy oracle (eval/oracle.py) against both backends
                 on 8,192 events at the settings of tests/test_oracle.py,
                 for every shedder.
  served_path    StreamRuntime with ingest admission, model refresh every
                 4 chunks, the carry guard, and snapshots every 4 chunks
                 plus the write-ahead log: 60,000 stock events at 1.4x in
                 3,000-event pushes, 1,024-event chunks, pSPICE.  Carry
                 sha256 and match sets equal across backends; a fresh
                 runtime recovered from disk reproduces the carry sha.
  tenant_lanes   MultiTenantRuntime with 8 lanes of the
                 examples/runtime_multitenant.py workload at N=2048,
                 16,384 events per lane: carries equal per lane.

Four-chip phases (``--chips 4``):

  sharded_engine  the soccer scenario (8 x Q3) pattern-parallel over 4
                  chips, 2 patterns each, no shedding, N=2048, 30,000
                  events, against run_engine on one chip.
  sharded_lanes   the 8-lane runtime on a 4-device mesh against the same
                  runtime without a mesh.

Each phase prints one JSON line; its compile and wall seconds are
information, not metrics.  The last line is ``{"ok": true, "device":
{...}}``.  Any mismatch or failure exits non-zero.  Without a TPU (and
without ``--rehearse``) it exits non-zero before running a phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

FULL = dict(N=2048, W=32, n_run=30_000, chunk_engine=5_000, oracle_n=8_192,
            served_n=60_000, push=3_000, chunk=1_024, lanes=8,
            lane_n=16_384, soccer_n=30_000, overload=1.4)
TINY = dict(N=64, W=8, n_run=1_200, chunk_engine=400, oracle_n=512,
            served_n=2_560, push=256, chunk=256, lanes=4, lane_n=1_024,
            soccer_n=400, overload=1.4)


class Mismatch(AssertionError):
    """A backend disagreed with the reference (or a phase saw no work)."""


def check(ok, what: str) -> None:
    if not ok:
        raise Mismatch(what)


class CompileClock:
    """Sums XLA backend-compile seconds and persistent-cache hits."""

    def __init__(self, monitoring):
        self.seconds, self.hits = 0.0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.hits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend (CPU tests)")
    ap.add_argument("--phase", default=None,
                    help="run only this phase (default: all)")
    ap.add_argument("--out", default=os.path.join(ROOT, "build",
                                                  "chip_smoke"),
                    help="scratch directory (persistence files)")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from jax import monitoring

    backend = jax.default_backend()
    if backend != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (backend {backend!r}); "
              "use --rehearse for a CPU rehearsal", file=sys.stderr)
        return 2
    cache_dir = None
    if not args.rehearse:
        from repro import compile_cache
        cache_dir = compile_cache.enable()
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    S = dict(TINY if args.rehearse else FULL)
    S["tpu"] = backend == "tpu"
    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out, exist_ok=True)
    clock = CompileClock(monitoring)

    phases = ([phase_sharded_engine, phase_sharded_lanes] if args.chips == 4
              else [phase_engine_parity, phase_oracle, phase_served_path,
                    phase_tenant_lanes])
    if args.phase is not None:
        phases = [f for f in phases if f.__name__ == "phase_" + args.phase]
        if not phases:
            print(f"chip_smoke: no phase {args.phase!r} with --chips "
                  f"{args.chips}", file=sys.stderr)
            return 2
    ctx = {}
    for phase in phases:
        c0, h0 = clock.mark()
        t0 = time.perf_counter()
        info = phase(S, ctx, args.out)
        c1, h1 = clock.mark()
        line = {"phase": phase.__name__[len("phase_"):], "ok": True,
                "compile_s": round(c1 - c0, 3), "cache_hits": h1 - h0,
                "wall_s": round(time.perf_counter() - t0, 3)}
        line.update(info)
        print(json.dumps(line), flush=True)
    if cache_dir is not None:
        print(json.dumps({"compile_cache": cache_dir,
                          "compile_s_total": round(clock.seconds, 3),
                          "cache_hits_total": clock.hits}), flush=True)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": args.chips}}), flush=True)
    return 0


# ---------------------------------------------------------------------------
# Shared helpers (imported lazily: the script must start without the repo)
# ---------------------------------------------------------------------------

BACKENDS = ("xla", "pallas_block")
SHEDDERS = ("none", "ebl", "pspice", "pmbl")


def _leaves_equal(a, b) -> bool:
    """Bitwise equality of two pytrees, leaf by leaf, on the host."""
    import jax
    import numpy as np
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape or x.dtype != y.dtype \
                or x.tobytes() != y.tobytes():
            return False
    return True


def _device_equal(a, b) -> bool:
    """Bitwise equality of two pytrees, compared on the device (large
    emitted match columns never cross to the host)."""
    import jax
    import jax.numpy as jnp

    def bits(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jax.lax.bitcast_convert_type(x, jnp.int32)
        return x
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        x.shape == y.shape and x.dtype == y.dtype
        and bool(jnp.array_equal(bits(x), bits(y)))
        for x, y in zip(la, lb))


def _split_raw(raw, n_head):
    head = dataclasses.replace(raw, n=n_head, type_id=raw.type_id[:n_head],
                               attr=raw.attr[:n_head],
                               group=raw.group[:n_head])
    tail = dataclasses.replace(raw, n=raw.n - n_head,
                               type_id=raw.type_id[n_head:],
                               attr=raw.attr[n_head:],
                               group=raw.group[n_head:])
    return head, tail


def _assert_kernel_compiled(S, cfg, model, ev, carry):
    """The chunk step for a Pallas backend carries a Mosaic kernel."""
    if not S["tpu"]:
        return
    import jax.numpy as jnp
    from repro.cep import engine as eng
    text = eng.run_engine_chunk.lower(cfg, model, ev, carry,
                                      jnp.int32(0)).compile().as_text()
    check("tpu_custom_call" in text,
          f"{cfg.backend} chunk step has no Mosaic kernel")


def _stock_setup(S):
    from repro.cep import patterns as pat
    from repro.cep import runner
    from repro.configs import pspice_paper as pp
    from repro.data import streams
    sc = streams.get_scenario("stock")
    specs = sc.specs()
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=S["N"], block_events=S["W"],
                                latency_bound=sc.latency_bound,
                                emit_matches=True, **pp.COST)
    return sc, specs, cp, cfg


def _built(S, ctx):
    """The stock model, built once per backend on the first 30% of the
    stream (the gather-stats kernel on pallas_block)."""
    if "built" in ctx:
        return ctx["built"]
    from repro.cep import runner
    from repro.data import streams
    sc, specs, cp, cfg = _stock_setup(S)
    n_warm = round(S["n_run"] * 3 / 7)
    raw_warm, raw_run = _split_raw(sc.raw(n=n_warm + S["n_run"]), n_warm)
    warm = streams.classify(specs, raw_warm, rate=1.0, seed=sc.seed)
    built = {b: runner.build_model(specs, dataclasses.replace(cfg,
                                                              backend=b),
                                   warm, bin_size=sc.bin_size, seed=sc.seed)
             for b in BACKENDS}
    x, k = built["xla"], built["pallas_block"]
    check(_leaves_equal((x.ut_stacked, x.ut_bins, x.f_model, x.g_model),
                        (k.ut_stacked, k.ut_bins, k.f_model, k.g_model))
          and x.max_rate == k.max_rate,
          "model built through the gather-stats kernel differs from xla")
    ctx["built"] = (sc, specs, cp, cfg, x, raw_run)
    return ctx["built"]


def _model(cp, cfg, built, events):
    import numpy as np
    from repro.cep import engine as eng
    return eng.make_model(cp, cfg, ut_tables=built.ut_stacked,
                          ut_bins=built.ut_bins, f_model=built.f_model,
                          g_model=built.g_model,
                          ebl_raw_mean=float(np.asarray(
                              events.ebl_raw).mean()))


# ---------------------------------------------------------------------------
# One-chip phases
# ---------------------------------------------------------------------------

def phase_engine_parity(S, ctx, out_dir):
    import jax.numpy as jnp
    import numpy as np
    from repro.cep import engine as eng
    from repro.core import overload as ovl
    from repro.data import streams
    from repro import runtime as RT
    sc, specs, cp, cfg, built, raw_run = _built(S, ctx)
    rate = built.max_rate * S["overload"]
    events = streams.classify(specs, raw_run, rate=rate, seed=sc.seed)
    n = S["n_run"]
    info = {"events": n, "N": S["N"], "W": S["W"],
            "max_rate": built.max_rate, "f_kind": int(built.f_model.kind)}
    # The four shedders on the built (LINEAR) latency model, and pSPICE
    # once more on an n·log2(n+1) model of the same slope at the steady
    # store size: Algorithm 1's Newton inverse and log2 in the kernel.
    f_lin = built.f_model
    f_nlogn = ovl.LatencyModel(
        a=jnp.float32(float(f_lin.a) / np.log2(built.steady_n_pm + 2.0)),
        b=f_lin.b, kind=jnp.int32(ovl.NLOGN))
    runs = [(sh, sh, f_lin) for sh in SHEDDERS]
    runs.append(("pspice_nlogn", "pspice", f_nlogn))
    for name, shedder, f_model in runs:
        cfgs = {b: dataclasses.replace(cfg, backend=b, shedder=shedder)
                for b in BACKENDS}
        model = _model(cp, cfgs["xla"], built, events)._replace(
            f_model=f_model)
        carry = {b: eng.init_carry(cfgs[b], seed=sc.seed) for b in BACKENDS}
        n_match = 0
        for start, piece in RT.iter_chunks(events, S["chunk_engine"]):
            if start == 0 and name == "pspice":
                _assert_kernel_compiled(S, cfgs["pallas_block"], model,
                                        piece, carry["pallas_block"])
            outs = {}
            for b in BACKENDS:
                carry[b], outs[b] = eng.run_engine_chunk(
                    cfgs[b], model, jax_copy(piece), carry[b],
                    jnp.int32(start))
            check(_device_equal(outs["xla"], outs["pallas_block"]),
                  f"{name}: step outputs (matches, l_e, shed, drop) "
                  f"differ at chunk {start}")
            n_match += int(jnp.sum(outs["xla"].match_open >= 0))
        check(_leaves_equal(carry["xla"], carry["pallas_block"]),
              f"{name}: final carry differs")
        c = carry["pallas_block"]
        calls = float(c.shed_calls)
        if shedder in ("pspice", "pmbl"):
            check(calls > 0, f"{name}: Algorithm 2 never fired")
        info[name] = {"matches": n_match,
                         "complex": float(np.asarray(c.complex_count).sum()),
                         "shed_calls": calls, "pms_shed": float(c.pms_shed),
                         "ebl_dropped": float(c.ebl_dropped)}
    return info


def jax_copy(tree):
    """A fresh device copy: ``run_engine_chunk`` donates its events."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.copy, tree)


def phase_oracle(S, ctx, out_dir):
    """The NumPy oracle at tests/test_oracle.py's shedder settings."""
    import numpy as np
    from repro.cep import engine as eng
    from repro.cep import patterns as pat
    from repro.cep import runner
    from repro.data import streams
    from repro.eval import oracle as orc
    cost = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4,
                c_shed_pm=1.5e-6, c_ebl=6e-5)
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    n = S["oracle_n"]
    out = {"events": n}
    for shedder in SHEDDERS:
        cfg = runner.default_config(
            cp, max_pms=48, latency_bound=0.005, shedder=shedder,
            emit_matches=True, shed_plan="sort", **cost)
        model = eng.make_model(cp, cfg)
        rate = 3.0 / (cfg.c_base + cfg.c_match * 0.3 * cfg.max_pms)
        raw = streams.gen_stock(n, num_symbols=50, pattern_symbols=4,
                                p_class=0.05, seed=100)
        ev = streams.classify(specs, raw, rate=rate, seed=0)
        o = orc.run_oracle(cfg, model, ev, seed=0)
        for b in BACKENDS:
            cfg_b = dataclasses.replace(cfg, backend=b)
            carry, outs = eng.run_engine(cfg_b, model, ev,
                                         eng.init_carry(cfg_b))
            tag = f"oracle/{shedder}/{b}"
            check(eng.match_sets(outs) == o.matches, f"{tag}: match sets")
            check(np.array_equal(np.asarray(carry.complex_count),
                                 o.complex_count)
                  and np.array_equal(np.asarray(carry.pms_created),
                                     o.pms_created)
                  and float(carry.pms_shed) == o.pms_shed
                  and float(carry.shed_calls) == o.shed_calls
                  and float(carry.overflow) == o.overflow
                  and float(carry.ebl_dropped) == o.ebl_dropped,
                  f"{tag}: counters")
            check(np.array_equal(np.asarray(outs.l_e), o.l_e)
                  and np.array_equal(np.asarray(outs.shed), o.shed)
                  and np.array_equal(np.asarray(outs.dropped), o.dropped),
                  f"{tag}: l_e / shed / drop trace")
        out[shedder] = {"matches": sum(len(m) for m in o.matches),
                        "shed_calls": o.shed_calls}
    return out


def _served_runtime(S, sc, specs, cp, cfg, built, events, backend, pdir):
    from repro import runtime as RT
    from repro.runtime import supervisor as SV
    cfg_b = dataclasses.replace(cfg, backend=backend, shedder="pspice",
                                gather_stats=True)
    model = _model(cp, cfg_b, built, events)
    rtc = RT.RuntimeConfig(
        chunk_size=S["chunk"],
        refresh=RT.RefreshConfig(every_chunks=4, min_observations=64.0,
                                 bin_size=sc.bin_size),
        ingest=RT.IngestConfig(admit_rate=1.2 * built.max_rate,
                               admit_burst=4096.0, seed=5),
        guard=RT.GuardConfig(check_every_chunks=1,
                             checkpoint_every_chunks=4),
        persist=RT.PersistConfig(dir=pdir, snapshot_every_chunks=4))
    return SV.MatchRuntime(cfg_b, model, rtc, specs=specs, seed=sc.seed)


def phase_served_path(S, ctx, out_dir):
    from repro.data import streams
    from repro import runtime as RT
    from repro.runtime import supervisor as SV
    sc, specs, cp, cfg, built, _ = _built(S, ctx)
    raw = sc.raw(n=S["served_n"], seed=sc.seed + 1)
    events = streams.classify(specs, raw, rate=built.max_rate * S["overload"],
                              seed=sc.seed)
    n, push = S["served_n"], S["push"]
    res = {}
    for b in BACKENDS:
        pdir = os.path.join(out_dir, f"served_{b}")
        srt = _served_runtime(S, sc, specs, cp, cfg, built, events, b, pdir)
        for s in range(0, n, push):
            srt.push(RT.slice_events(events, s, min(s + push, n)))
        srt.flush()
        res[b] = (SV.carry_sha(srt), srt.matches, srt)
    (sha_x, m_x, rt_x), (sha_k, m_k, rt_k) = res["xla"], res["pallas_block"]
    check(sha_x == sha_k, "served path: carry sha256 differs")
    check(m_x == m_k, "served path: match sets differ")
    agg = rt_k.telemetry.aggregate()
    check(float(rt_k.carry.shed_calls) > 0,
          "served path: Algorithm 2 never fired")
    # Recovery: a kernel-backend runtime abandoned two thirds of the way
    # in (its directory is what a crash leaves), then a fresh runtime
    # restores the newest snapshot, replays the write-ahead log tail and
    # finishes the stream: it must end in the live run's exact state.
    pdir = os.path.join(out_dir, "served_recovered")
    cut = (n // push) * 2 // 3 * push
    dead = _served_runtime(S, sc, specs, cp, cfg, built, events,
                           "pallas_block", pdir)
    for s in range(0, cut, push):
        dead.push(RT.slice_events(events, s, min(s + push, n)))
    dead.persist.wal.close()
    del dead
    rec = _served_runtime(S, sc, specs, cp, cfg, built, events,
                          "pallas_block", pdir)
    report = rec.recover_from_disk()
    for s in range(rec.persist.wal.next_record_id * push, n, push):
        rec.push(RT.slice_events(events, s, min(s + push, n)))
    rec.flush()
    check(report["snapshot_chunk"] is not None
          and report["replayed_records"] > 0,
          f"recovery restored no snapshot or replayed no log: {report}")
    check(SV.carry_sha(rec) == sha_k and rec.matches == m_k,
          "recovery: carry sha256 / matches differ from the live run")
    return {"events": n, "carry_sha": sha_k,
            "matches": sum(len(m) for m in m_k),
            "chunks": agg["n_chunks"], "refreshes": agg["refreshes"],
            "shed_calls": float(rt_k.carry.shed_calls),
            "recovery": {k: report[k] for k in (
                "snapshot_chunk", "replayed_records")}}


def _lanes_workload(S):
    """examples/runtime_multitenant.py's tenants at N PM slots."""
    from repro.cep import engine as eng
    from repro.cep import patterns as pat
    from repro.cep import runner
    from repro.data import streams
    from repro import runtime as RT
    cost = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4,
                c_shed_pm=1.5e-6, c_ebl=6e-5)
    L, n = S["lanes"], S["lane_n"]
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=S["N"], latency_bound=0.02,
                                gather_stats=True, shedder="pspice",
                                block_events=S["W"], **cost)
    model = eng.make_model(cp, cfg)
    # The example's own arrival rates (sized for its 128-slot store), so
    # the drift overloads the operator and the shedder works.
    rate = 1.0 / (cfg.c_base + cfg.c_match * 0.3 * 128)
    evs = []
    for lane in range(L):
        raw = streams.gen_stock_drift(n, num_symbols=50, pattern_symbols=4,
                                      p_class=0.03, p_class_end=0.10,
                                      seed=100 + lane)
        evs.append(streams.classify(specs, raw, rate=rate * (1 + 0.2 * lane),
                                    rate_end=4.0 * rate, seed=lane))
    rtc = RT.RuntimeConfig(chunk_size=S["chunk"], refresh=RT.RefreshConfig(
        every_chunks=4, min_observations=256, decay=0.5))
    return specs, cfg, model, RT.stack(evs), rtc


def _run_lanes(S, specs, cfg, model, evL, rtc, mesh=None):
    from repro import runtime as RT
    L, n, push = S["lanes"], S["lane_n"], S["push"]
    mt = RT.MultiTenantRuntime(cfg, RT.broadcast_model(model, L),
                               num_lanes=L, rt=rtc, specs=specs, mesh=mesh)
    for s in range(0, n, push):
        mt.push(RT.slice_events(evL, s, min(s + push, n), axis=1),
                flush=(s + push >= n))
    return mt


def phase_tenant_lanes(S, ctx, out_dir):
    import numpy as np
    from repro import runtime as RT
    specs, cfg, model, evL, rtc = _lanes_workload(S)
    mts = {b: _run_lanes(S, specs, dataclasses.replace(cfg, backend=b),
                         model, evL, rtc) for b in BACKENDS}
    for lane in range(S["lanes"]):
        check(_leaves_equal(RT.unstack_lane(mts["xla"].carry, lane),
                            RT.unstack_lane(mts["pallas_block"].carry,
                                            lane)),
              f"tenant lane {lane}: carry differs")
    c = mts["pallas_block"].carry
    return {"lanes": S["lanes"], "events_per_lane": S["lane_n"],
            "shed_calls": np.asarray(c.shed_calls).tolist(),
            "complex": np.asarray(c.complex_count).sum(axis=1).tolist()}


# ---------------------------------------------------------------------------
# Four-chip phases
# ---------------------------------------------------------------------------

def _span(x) -> int:
    return len(x.sharding.device_set)


def phase_sharded_engine(S, ctx, out_dir):
    import jax.numpy as jnp
    import numpy as np
    from repro.cep import engine as eng
    from repro.cep import patterns as pat
    from repro.cep import runner
    from repro.configs import pspice_paper as pp
    from repro.data import streams
    from repro.dist import sharding as SH
    sc = streams.get_scenario("soccer")
    specs = sc.specs()
    check(len(specs) == 8, "soccer scenario must have 8 Q3 patterns")
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=S["N"], block_events=S["W"],
                                latency_bound=sc.latency_bound,
                                emit_matches=True, shedder="none",
                                backend="pallas_block", **pp.COST)
    model = eng.make_model(cp, cfg)
    ev = streams.classify(specs, sc.raw(n=S["soccer_n"]), rate=1.0,
                          seed=sc.seed)
    mesh = SH.make_mesh((4,), ("data",))
    c_sh, o_sh = SH.run_engine_sharded(cfg, model, ev,
                                       eng.init_carry(cfg, seed=sc.seed),
                                       mesh=mesh)
    check(_span(c_sh.complex_count) == 4 and _span(o_sh.match_open) == 4,
          "pattern-sharded outputs do not span 4 devices")
    c_1, o_1 = eng.run_engine(cfg, model, ev,
                              eng.init_carry(cfg, seed=sc.seed))
    check(_span(o_1.match_open) == 1, "reference run is not on one device")
    for name, a, b in (("match_open", o_sh.match_open, o_1.match_open),
                       ("match_bind", o_sh.match_bind, o_1.match_bind),
                       ("complex_count", c_sh.complex_count,
                        c_1.complex_count),
                       ("pms_created", c_sh.pms_created, c_1.pms_created)):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"sharded engine: {name} differs from one device")
    return {"patterns": cfg.num_patterns, "events": S["soccer_n"],
            "devices": _span(o_sh.match_open),
            "matches": int(jnp.sum(o_1.match_open >= 0)),
            "complex": float(np.asarray(c_1.complex_count).sum())}


def phase_sharded_lanes(S, ctx, out_dir):
    import jax
    from repro.dist import sharding as SH
    from repro import runtime as RT
    specs, cfg, model, evL, rtc = _lanes_workload(S)
    cfg = dataclasses.replace(cfg, backend="pallas_block")
    mesh = SH.make_mesh((4,), ("data",))
    mt_mesh = _run_lanes(S, specs, cfg, model, evL, rtc, mesh=mesh)
    spans = {_span(x) for x in jax.tree.leaves(mt_mesh.carry)}
    check(spans == {4}, f"sharded lanes: carry spans {spans} devices")
    mt_1 = _run_lanes(S, specs, cfg, model, evL, rtc)
    for lane in range(S["lanes"]):
        check(_leaves_equal(RT.unstack_lane(mt_mesh.carry, lane),
                            RT.unstack_lane(mt_1.carry, lane)),
              f"sharded lanes: lane {lane} differs from the unsharded run")
    return {"lanes": S["lanes"], "events_per_lane": S["lane_n"],
            "devices": 4}


if __name__ == "__main__":
    sys.exit(main())
