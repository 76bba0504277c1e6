"""The plain reference against hand-worked streams, and against the
program's engine where nothing is shed (the reference must then name every
match the engine emits, and the final store exactly)."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import reference as REF  # noqa: E402
import system as S  # noqa: E402
import traffic  # noqa: E402

Q1 = {"queries": [{"query": "Q1", "num_symbols": 3, "window_size": 6}]}


def _stock(types, attrs):
    t = np.asarray(types, np.int32)
    return t, np.asarray(attrs, np.int32), np.zeros_like(t)


def test_q1_by_hand():
    # symbol 0 rises at 0 and 4 (opens), symbol 1 at 2 and 6, symbol 2 at
    # 3 and 9.  PM@0: 2 -> state 2, 3 -> complete at 3.  PM@4: 6 -> state
    # 2, then its window (events 5..9) closes before symbol 2 rises at 9
    # (9 - 4 = 5 < 6: inside), so it completes at 9.
    types = [0, 5, 1, 2, 0, 7, 1, 8, 8, 2, 8]
    attrs = [1, 1, 1, 1, 1, 1, 1, 0, 0, 1, 0]
    t, a, g = _stock(types, attrs)
    pms = REF.reference_pms(Q1, t, a, g, E=10)
    f = REF.fates(Q1, pms, E=10)[0]
    assert pms[0]["open"].tolist() == [0, 4]
    assert f["comp"].tolist() == [3, 9]
    assert f["done"].tolist() == [True, True]
    # At E=7 the second PM is open, in state 2, and nothing expired yet.
    f7 = REF.fates(Q1, REF.reference_pms(Q1, t, a, g, E=7), E=7)[0]
    assert f7["alive"].tolist() == [False, True]
    assert f7["state"][1] == 2


def test_q1_window_closes():
    # PM@0 reaches state 2 at 1 but symbol 2 rises only at 6: 6 - 0 = 6
    # is outside a window of 6 events, so the PM expires.
    t, a, g = _stock([0, 1, 9, 9, 9, 9, 2], [1, 1, 0, 0, 0, 0, 1])
    f = REF.fates(Q1, REF.reference_pms(Q1, t, a, g, E=6), E=6)[0]
    assert f["expired"].tolist() == [True]
    assert f["done"].tolist() == [False]


def test_q3_by_hand():
    cfg = {"queries": [{"query": "Q3", "any_n": 2, "window_size": 5}]}
    # striker 0 in possession at 0; defenders 3, 3 (repeat), 4 bound to
    # striker 0 at 1, 2, 3; striker 1 at 4; defender 5 bound to 1 at 5.
    attr = np.array([2, 1, 1, 1, 2, 1, 0], np.int32)
    tid = np.array([0, 3, 3, 4, 1, 5, -1], np.int32)
    grp = np.array([0, 0, 0, 0, 1, 1, 1], np.int32)
    pms = REF.reference_pms(cfg, tid, attr, grp, E=6)
    f = REF.fates(cfg, pms, E=6)[0]
    assert pms[0]["open"].tolist() == [0, 4]
    assert pms[0]["bind"].tolist() == [0, 1]
    assert f["comp"].tolist() == [3, -1]          # distinct 3 then 4
    assert f["alive"].tolist() == [False, True]
    assert f["state"][1] == 2                      # one distinct defender


def test_admission_bucket():
    assert REF.admitted([(10, 0.0), (10, 1.0)], rate=10.0, burst=10) == 20
    assert REF.admitted([(10, 0.0), (10, 0.5)], rate=10.0, burst=10) == -1


def _engine_run(config, stream, n, N):
    """The program's engine, nothing shed, every match emitted."""
    import jax.numpy as jnp
    from repro.cep import engine as eng
    from repro.cep import patterns as pat
    from repro.cep import runner
    import system as S
    specs = S.specs_of(config)
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=N, emit_matches=True,
                                shedder="none", backend="xla")
    cl = traffic.Classifier(config["queries"], stream, rate=100.0)
    ev = eng.EventBatch(**{k: jnp.asarray(v) for k, v in
                           cl.columns(0, n).items()})
    carry, outs = eng.run_engine(cfg, eng.make_model(cp, cfg), ev,
                                 eng.init_carry(cfg))
    c = carry
    return dict(
        active=np.asarray(c.pms.active), state=np.asarray(c.pms.state),
        open_idx=np.asarray(c.pms.open_idx), bind=np.asarray(c.pms.bind),
        idset=np.asarray(c.pms.idset),
        complex_count=np.asarray(c.complex_count).astype(np.int64),
        pms_created=np.asarray(c.pms_created).astype(np.int64),
        pms_shed=int(c.pms_shed), overflow=int(c.overflow),
        events_processed=n, matches=eng.match_sets(outs),
        chunks_run=0, refresh_count=0, l_e_max=0.0,
        ut_tables=np.zeros((len(specs), 1, cfg.max_states), np.float32),
        refresh_obs=(np.asarray(c.obs_counts), np.asarray(c.obs_rewards)))


def _exact(config, raw, prog):
    """The exact comparisons, and the quality number (nothing shed: no
    match lost); the engine alone has no refresh and no simulated
    latency to hold."""
    checks, info = REF.compare(config, (raw.type_id, raw.attr, raw.group),
                               raw.n, prog)
    return {k: v for k, v in checks.items()
            if k not in ("table_gap", "latency_x_bound")}, info


@pytest.mark.parametrize("name", ["stock_q1x3", "soccer_q3x8"])
def test_reference_equals_the_engine_without_shedding(name):
    import json
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        config = json.load(f)
    n = 8000 if name == "stock_q1x3" else 1500
    stream = traffic.Stream(config["dataset"], seed=2**31 + 11)
    raw = stream.raw(0, n)
    prog = _engine_run(config, stream, n, N=256)
    assert prog["overflow"] == 0
    checks, info = _exact(config, raw, prog)
    assert all(v == 0 for v, _ in checks.values()), checks
    assert info["completed"] == int(prog["complex_count"].sum()) > 0
    # Moving one emitted match by one event is caught.
    p = next(i for i, m in enumerate(prog["matches"]) if m)
    o, b, e = sorted(prog["matches"][p])[0]
    prog["matches"][p] = (prog["matches"][p] - {(o, b, e)}) | {(o, b, e + 1)}
    checks, _ = _exact(config, raw, prog)
    assert checks["unsound_matches"][0] == 1


@pytest.mark.parametrize("name", ["stock_q1x3", "soccer_q3x8"])
def test_fit_tables_equals_the_programs_refresh(name):
    # The plain float64 fit against the program's own table build, on the
    # same counts (on the CPU, in float32, they agree to rounding).
    import json
    from repro.core import markov, utility
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        config = json.load(f)
    specs = S.specs_of(config)
    M = max(s.num_states for s in specs)
    rng = np.random.default_rng(5)
    counts = np.zeros((len(specs), M, M), np.float32)
    for p, spec in enumerate(specs):
        m = spec.num_states
        for s in range(1, m - 1):
            counts[p, s, s] = rng.integers(500, 5000)
            counts[p, s, s + 1] = rng.integers(5, 50)
    rewards = counts * np.float32(config["engine"]["cost"]["c_match"])
    ref = REF.fit_tables(config, counts, rewards)
    e = config["engine"]
    for p, spec in enumerate(specs):
        m = spec.num_states
        stats = markov.TransitionStats(counts=counts[p, :m, :m],
                                       reward_sum=rewards[p, :m, :m])
        T = markov.estimate_transition_matrix(stats)
        R = markov.estimate_reward_matrix(
            stats, default_reward=e["cost"]["c_match"])
        got = np.asarray(utility.build_utility_table(
            T, R, window_size=spec.window_size,
            bin_size=e["bin_size"]).table)
        np.testing.assert_allclose(got[:, 1:m - 1], ref[p][:, 1:m - 1],
                                   rtol=2e-3)
    deployed = np.zeros((len(specs), max(r.shape[0] for r in ref), M))
    for p, r in enumerate(ref):
        deployed[p, :r.shape[0], :r.shape[1]] = r
    assert REF.table_gap(config, deployed, counts, rewards) == 0.0
    stale = deployed.copy()
    stale[0] *= 1.5
    assert REF.table_gap(config, stale, counts, rewards) > 0.1
