"""The system under test, assembled from a configuration file.

Everything here calls the program (``repro``): the pattern compiler, the
model builder (the warm prefix runs through the gather-stats kernel), the
engine configuration and the served runtime with its refresh, guard,
admission and persistence.  The benchmark hands it generated event columns
and reads back its outputs, spans and counters.
"""
from __future__ import annotations

import dataclasses
import os

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


@dataclasses.dataclass
class System:
    runtime: object          # StreamRuntime | MatchRuntime
    cfg: object              # repro EngineConfig
    classifier: traffic.Classifier
    stream: traffic.Stream
    max_rate: float          # model-built max throughput (simulated ev/s)
    emits: bool              # MatchRuntime: matches emitted and decoded


def sizes(config: dict, rehearse: bool) -> dict:
    e, s = config["engine"], config["served"]
    d = dict(max_pms=e["max_pms"], block_events=e["block_events"],
             warm_events=config["model_build"]["warm_events"],
             chunk=s["chunk"], limits=config["limits"])
    if rehearse:
        d.update(REHEARSAL)
    return d


def build(config: dict, seeds: dict, persist_dir: str,
          rehearse: bool = False) -> System:
    """Build the model on a warm prefix of its own, then the served
    runtime; the stream it serves is generated on demand, classified per
    push."""
    import jax.numpy as jnp
    from repro.cep import engine as eng
    from repro.cep import patterns as pat
    from repro.cep import runner
    from repro import runtime as RT
    from repro.runtime import persist as PS
    from repro.runtime import supervisor as SV

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

    # Model build: the warm prefix at a rate of one event per simulated
    # second (never overloaded), through the gather-stats kernel.
    wc = traffic.Classifier(config["queries"],
                            traffic.Stream(config["dataset"], seeds["warm"]),
                            rate=1.0)
    warm = eng.EventBatch(**{k: jnp.asarray(v) for k, v in
                             wc.columns(0, sz["warm_events"]).items()})
    built = runner.build_model(specs, cfg, warm,
                               bin_size=e["bin_size"], seed=seeds["carry"])

    stream = traffic.Stream(config["dataset"], seeds["stream"])
    rate = built.max_rate * config["model_build"]["overload"]
    cl = traffic.Classifier(config["queries"], stream, rate=rate)
    head = cl.columns(0, 1 << 16)
    model = eng.make_model(cp, cfg, ut_tables=built.ut_stacked,
                           ut_bins=built.ut_bins, f_model=built.f_model,
                           g_model=built.g_model,
                           ebl_raw_mean=float(head["ebl_raw"].mean()))

    kw = {}
    if "admission" in s:
        a = s["admission"]
        kw["ingest"] = RT.IngestConfig(
            admit_rate=a["rate_x_max"] * built.max_rate,
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
    cls = SV.MatchRuntime if emits else RT.StreamRuntime
    rt = cls(cfg, model, rtc, specs=specs, seed=seeds["carry"])
    return System(runtime=rt, cfg=cfg, classifier=cl, stream=stream,
                  max_rate=built.max_rate, emits=emits)


def batch(system: System, lo: int, hi: int):
    """Events [lo, hi) of the stream as a device ``EventBatch``."""
    import jax
    from repro.cep import engine as eng
    cols = system.classifier.columns(lo, hi)
    return jax.device_put(eng.EventBatch(**cols))


def watch_refresh(rt) -> None:
    """Keep, on the host, the observations each model refresh fits from,
    so that the reference can fit the same tables after the window."""
    rt.refresh_obs = None

    def refresh():
        obs = (rt.carry.obs_counts, rt.carry.obs_rewards)
        did = type(rt)._maybe_refresh(rt)     # a planted fault's, if any
        if did:
            rt.refresh_obs = tuple(np.asarray(x) for x in obs)
        return did
    rt._maybe_refresh = refresh


def final_state(system: System) -> dict:
    """The program's outputs that the reference checks, on the host."""
    rt = system.runtime
    c = rt.carry
    out = dict(
        active=np.asarray(c.pms.active), state=np.asarray(c.pms.state),
        open_idx=np.asarray(c.pms.open_idx), bind=np.asarray(c.pms.bind),
        idset=np.asarray(c.pms.idset),
        complex_count=np.asarray(c.complex_count).astype(np.int64),
        pms_created=np.asarray(c.pms_created).astype(np.int64),
        pms_shed=int(np.asarray(c.pms_shed)),
        overflow=int(np.asarray(c.overflow)),
        shed_calls=int(np.asarray(c.shed_calls)),
        events_processed=int(rt.events_processed),
        chunks_run=int(rt._chunk_i),
        refresh_count=int(rt.refresh_state.refresh_count),
        # Where no refresh ran, the tables are held to the observations
        # the program has now.
        refresh_obs=getattr(rt, "refresh_obs", None) or (
            np.asarray(c.obs_counts), np.asarray(c.obs_rewards)),
        ut_tables=np.asarray(rt.model.ut_tables),
        matches=[set(m) for m in rt.matches] if system.emits else None)
    if rt.ingest is not None:
        out["admitted"] = int(rt.ingest.total_admitted)
    return out
