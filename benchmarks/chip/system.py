"""The system under test, assembled from a configuration file.

Everything here calls the program (``repro``): the pattern compiler, the
model builder (the warm prefix runs through the gather-stats kernel), the
engine configuration and the served runtime with its refresh, guard,
admission and persistence.  The benchmark hands it generated event columns
and reads back its outputs, spans and counters.

``served.runtime`` picks the runtime: ``"stream"`` (``StreamRuntime``,
counts only), ``"match"`` (``MatchRuntime``: matches emitted and decoded)
or ``"lanes"`` (``MultiTenantRuntime``: L tenant lanes, counts only).  A
lane configuration adds ``served.lanes``::

    "lanes": {"count": 32,
              "dataset": {"p_class": [0.06, 0.08, 0.1, 0.12]},
              "overload": [1.2, 1.4, 1.6]}

``count`` is L.  ``dataset`` overrides keys of the top-level ``dataset``
and ``overload`` overrides ``model_build.overload``, each as a list cycled
over the lanes: lane ``l`` takes entry ``l % len(list)``.  Each lane is a
tenant of its own: its stream is seeded ``(stream seed, l)``, its model is
built from its own warm prefix (seeded ``(warm seed, l)``), and its
arrivals run at its built max rate times its overload.  Admission, where
configured, is the front end's queue per lane, all at ``rate_x_max`` times
the largest lane max rate.  On a cell of more than one chip the lanes
shard over a ``data`` mesh of the cell's chips, ``L / chips`` to a chip;
on one chip they run the vmapped lane step.  Refused (``ValueError``): a
``lanes`` block under another runtime (the match runtime has no lanes),
``persist`` or any other ``served`` key the lane runtime is not given
here, an override of a key the dataset lacks, and an L that the cell's
chips do not divide.
"""
from __future__ import annotations

import dataclasses
import os
import time

import numpy as np

import traffic

# Sizes of the CPU rehearsal (tests only): the configuration's shapes at
# toy scale, so that every path runs with the kernels interpreted.
# Quality at toy scale is noise (a toy window completes a handful of
# matches), so the rehearsal holds the limits set from readings to limits
# of its own.
REHEARSAL = dict(max_pms=64, block_events=8, warm_events=512, chunk=64,
                 limits=dict(lost_per_found=30.0, table_gap=0.01,
                             latency_x_bound=1.1))

# What a lane configuration may give under ``served``.
LANE_SERVED = ("runtime", "chunk", "refresh", "lanes", "admission", "guard")
LANE_KEYS = ("count", "dataset", "overload")


def specs_of(config: dict):
    from repro.cep import patterns as pat
    out = []
    for q in config["queries"]:
        if q["query"] == "Q1":
            out.append(pat.make_q1(window_size=q["window_size"],
                                   num_symbols=q["num_symbols"]))
        elif q["query"] == "Q3":
            out.append(pat.make_q3(any_n=q["any_n"],
                                   window_size=q["window_size"]))
        else:
            raise ValueError(f"unknown query {q['query']!r}")
    return out


def lane_configs(config: dict, chips: int) -> list[dict] | None:
    """Per lane, the configuration with that lane's dataset and overload;
    None where the runtime has no lanes.  Raises ``ValueError`` for what
    the lane runtime or this harness does not support."""
    s = config["served"]
    if s["runtime"] != "lanes":
        if "lanes" in s:
            raise ValueError(f"served.lanes given, but runtime "
                             f"{s['runtime']!r} has no lanes")
        if chips != 1:
            raise ValueError(f"a {s['runtime']!r} runtime runs on one "
                             f"chip; the cell asks for {chips}")
        return None
    extra = sorted(set(s) - set(LANE_SERVED))
    if extra:
        raise ValueError(f"served keys {extra} are not supported with "
                         f"lanes (the lane runtime is counts only and "
                         f"keeps no write-ahead log or snapshots)")
    ln = s["lanes"]
    extra = sorted(set(ln) - set(LANE_KEYS))
    if extra:
        raise ValueError(f"unknown served.lanes keys {extra}")
    L = int(ln["count"])
    if L < 1 or L % chips:
        raise ValueError(f"{L} lanes cannot be spread evenly over "
                         f"{chips} chips")
    over = ln.get("dataset", {})
    unknown = sorted(k for k in over if k not in config["dataset"]
                     or k == "name")
    if unknown:
        raise ValueError(f"served.lanes.dataset overrides {unknown}, which "
                         f"the dataset does not have")
    lists = dict(over, overload=ln.get(
        "overload", [config["model_build"]["overload"]]))
    if any(not isinstance(v, list) or not v for v in lists.values()):
        raise ValueError("each per-lane override is a non-empty list")
    out = []
    for lane in range(L):
        pick = {k: v[lane % len(v)] for k, v in lists.items()}
        overload = pick.pop("overload")
        out.append(dict(config, dataset=dict(config["dataset"], **pick),
                        model_build=dict(config["model_build"],
                                         overload=overload)))
    return out


@dataclasses.dataclass
class System:
    runtime: object          # StreamRuntime | MatchRuntime | MultiTenant...
    cfg: object              # repro EngineConfig
    configs: list            # the configuration of each lane (one: no lanes)
    classifiers: list        # traffic.Classifier per lane (one: no lanes)
    max_rates: list          # model-built max throughput per lane (sim ev/s)
    emits: bool              # MatchRuntime: matches emitted and decoded
    lanes: int | None        # L of a lane runtime, None otherwise
    devices: int             # devices the runtime spreads its lanes over
    models_s: float          # wall seconds of the model builds


def sizes(config: dict, rehearse: bool) -> dict:
    e, s = config["engine"], config["served"]
    d = dict(max_pms=e["max_pms"], block_events=e["block_events"],
             warm_events=config["model_build"]["warm_events"],
             chunk=s["chunk"], limits=config["limits"])
    if rehearse:
        d.update(REHEARSAL)
    return d


def _lane_seed(seed: int, lane: int | None):
    return seed if lane is None else (seed, lane)


def build(config: dict, seeds: dict, persist_dir: str, chips: int = 1,
          rehearse: bool = False) -> System:
    """Build each lane's model on a warm prefix of its own, then the
    served runtime; the streams it serves are generated on demand,
    classified per push."""
    import jax
    import jax.numpy as jnp
    from repro.cep import engine as eng
    from repro.cep import patterns as pat
    from repro.cep import runner
    from repro import runtime as RT
    from repro.runtime import persist as PS
    from repro.runtime import supervisor as SV

    lanes = lane_configs(config, chips)
    sz = sizes(config, rehearse)
    e, s = config["engine"], config["served"]
    specs = specs_of(config)
    cp = pat.compile_patterns(specs)
    emits = s["runtime"] == "match"
    cfg = runner.default_config(
        cp, max_pms=sz["max_pms"], block_events=sz["block_events"],
        latency_bound=e["latency_bound"], backend=e["backend"],
        shedder=e["shedder"], emit_matches=emits, gather_stats=True,
        **e["cost"])

    t0 = time.perf_counter()
    models, classifiers, max_rates = [], [], []
    for lane, lc in (enumerate(lanes) if lanes else [(None, config)]):
        # Model build: the warm prefix at a rate of one event per
        # simulated second (never overloaded), through the gather-stats
        # kernel.
        wc = traffic.Classifier(
            lc["queries"], traffic.Stream(lc["dataset"],
                                          _lane_seed(seeds["warm"], lane)),
            rate=1.0)
        warm = eng.EventBatch(**{k: jnp.asarray(v) for k, v in
                                 wc.columns(0, sz["warm_events"]).items()})
        built = runner.build_model(
            specs, cfg, warm, bin_size=e["bin_size"],
            seed=seeds["carry"] + (lane or 0))
        stream = traffic.Stream(lc["dataset"],
                                _lane_seed(seeds["stream"], lane))
        rate = built.max_rate * lc["model_build"]["overload"]
        cl = traffic.Classifier(lc["queries"], stream, rate=rate)
        head = cl.columns(0, 1 << 16)
        models.append(eng.make_model(
            cp, cfg, ut_tables=built.ut_stacked, ut_bins=built.ut_bins,
            f_model=built.f_model, g_model=built.g_model,
            ebl_raw_mean=float(head["ebl_raw"].mean())))
        classifiers.append(cl)
        max_rates.append(built.max_rate)
    jax.block_until_ready(models)
    models_s = time.perf_counter() - t0

    kw = {}
    if "admission" in s:
        a = s["admission"]
        kw["ingest"] = RT.IngestConfig(
            admit_rate=a["rate_x_max"] * max(max_rates),
            admit_burst=float(a["burst"]), seed=seeds["admission"])
    if "guard" in s:
        kw["guard"] = RT.GuardConfig(
            check_every_chunks=s["guard"]["check_every_chunks"],
            checkpoint_every_chunks=s["guard"]["checkpoint_every_chunks"])
    if "persist" in s:
        os.makedirs(persist_dir, exist_ok=True)
        kw["persist"] = PS.PersistConfig(
            dir=persist_dir,
            snapshot_every_chunks=s["persist"]["snapshot_every_chunks"],
            wal_fsync_every=s["persist"]["wal_fsync_every"])
    rtc = RT.RuntimeConfig(
        chunk_size=sz["chunk"],
        refresh=RT.RefreshConfig(
            every_chunks=s["refresh"]["every_chunks"],
            min_observations=s["refresh"]["min_observations"],
            bin_size=e["bin_size"]), **kw)
    n_dev = 1
    if lanes:
        from repro.dist import sharding as SH
        n_dev = min(chips, len(jax.devices())) if rehearse else chips
        mesh = SH.make_mesh((n_dev,), ("data",),
                            devices=jax.devices()[:n_dev]) \
            if n_dev > 1 else None
        rt = RT.MultiTenantRuntime(cfg, RT.stack(models),
                                   num_lanes=len(lanes), rt=rtc,
                                   specs=specs, seed=seeds["carry"],
                                   mesh=mesh)
    else:
        cls = SV.MatchRuntime if emits else RT.StreamRuntime
        rt = cls(cfg, models[0], rtc, specs=specs, seed=seeds["carry"])
    return System(runtime=rt, cfg=cfg, configs=lanes or [config],
                  classifiers=classifiers,
                  max_rates=max_rates, emits=emits,
                  lanes=len(lanes) if lanes else None, devices=n_dev,
                  models_s=models_s)


def batch(system: System, lo: int, hi: int):
    """Events [lo, hi) of the stream as a device ``EventBatch``; with
    lanes, events [lo, hi) of every lane's stream, lane-stacked
    ``(L, hi - lo, ...)``."""
    import jax
    from repro.cep import engine as eng
    if system.lanes is None:
        cols = system.classifiers[0].columns(lo, hi)
    else:
        per = [c.columns(lo, hi) for c in system.classifiers]
        cols = {k: np.stack([p[k] for p in per]) for k in per[0]}
    return jax.device_put(eng.EventBatch(**cols))


def watch_refresh(rt) -> None:
    """Keep, on the host, the observations each model refresh fits from,
    so that the reference can fit the same tables after the window; with
    lanes, per lane, from the refreshes that lane's gates let through."""
    lanes = getattr(rt, "num_lanes", None)
    rt.refresh_obs = None if lanes is None else [None] * lanes

    def refresh():
        obs = (rt.carry.obs_counts, rt.carry.obs_rewards)
        if lanes is None:
            did = type(rt)._maybe_refresh(rt)   # a planted fault's, if any
            if did:
                rt.refresh_obs = tuple(np.asarray(x) for x in obs)
            return did
        before = [st.refresh_count for st in rt.refresh_state]
        did = type(rt)._maybe_refresh(rt)
        if did:
            c, r = (np.asarray(x) for x in obs)
            for lane, st in enumerate(rt.refresh_state):
                if st.refresh_count > before[lane]:
                    rt.refresh_obs[lane] = (c[lane], r[lane])
        return did
    rt._maybe_refresh = refresh


def final_state(system: System) -> list[dict]:
    """The program's outputs that the reference checks, on the host: one
    dict per lane (one in all where the runtime has no lanes)."""
    import jax
    rt = system.runtime
    c = jax.device_get(rt.carry)
    ut = np.asarray(rt.model.ut_tables)
    L = system.lanes
    refresh_states = rt.refresh_state if L else [rt.refresh_state]
    obs = rt.refresh_obs if L else [rt.refresh_obs]
    out = []
    for lane in (range(L) if L else [None]):
        def at(x):
            return np.asarray(x if lane is None else x[lane])
        i = lane or 0
        d = dict(
            active=at(c.pms.active), state=at(c.pms.state),
            open_idx=at(c.pms.open_idx), bind=at(c.pms.bind),
            idset=at(c.pms.idset),
            complex_count=at(c.complex_count).astype(np.int64),
            pms_created=at(c.pms_created).astype(np.int64),
            pms_shed=int(at(c.pms_shed)), overflow=int(at(c.overflow)),
            shed_calls=int(at(c.shed_calls)),
            # Lanes advance in lockstep: each processed its share.
            events_processed=int(rt.events_processed) // (L or 1),
            chunks_run=int(rt._chunk_i),
            refresh_count=int(refresh_states[i].refresh_count),
            # Where no refresh ran, the tables are held to the
            # observations the program has now.
            refresh_obs=obs[i] or (at(c.obs_counts), at(c.obs_rewards)),
            ut_tables=at(ut),
            matches=[set(m) for m in rt.matches] if system.emits else None)
        if rt.ingest is not None:
            q = rt.ingest if lane is None else rt.ingest.queues[lane]
            d["admitted"] = int(q.total_admitted)
        out.append(d)
    return out
