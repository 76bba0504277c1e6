"""The model fit as one compiled program over all queries
(``utility.fit_model`` via ``runner.fit_model``; DESIGN.md §7).

Held against the per-query definition it replaces —
``estimate_transition_matrix`` / ``estimate_reward_matrix`` /
``build_utility_table`` / ``stack_tables`` — on both deployment shapes:
soccer (eight Q3 queries, 4 to 11 states padded to 11, 3 bins each) and
stock (three Q1 queries, 11 states, 10/19/38 bins).
"""
import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import dispatch, pjit

from repro import runtime as RT
from repro.analysis import tracing
from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.core import markov, overload as ovl, utility as util
from repro.data import streams

COST = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4, c_shed_pm=1.5e-6,
            c_ebl=6e-5)
BIN = 64
SHAPES = {
    "soccer": lambda: [pat.make_q3(any_n=n, window_size=150)
                       for n in range(2, 10)],
    "stock": lambda: [pat.make_q1(window_size=w, num_symbols=10)
                      for w in (600, 1200, 2400)],
}


def _observations(specs, M, seed):
    """Random counts and reward sums in each query's live block, with one
    live row per query that has no observations."""
    rng = np.random.default_rng(seed)
    P = len(specs)
    oc = np.zeros((P, M, M), np.float32)
    orw = np.zeros((P, M, M), np.float32)
    for p, s in enumerate(specs):
        m = s.num_states
        c = (rng.poisson(3.0, (m, m))
             * (rng.random((m, m)) < 0.6)).astype(np.float32)
        c[rng.integers(0, m - 1)] = 0.0
        oc[p, :m, :m] = c
        orw[p, :m, :m] = c * rng.random((m, m)).astype(np.float32) * 1e-4
    return oc, orw


def _carry(cfg, oc, orw, seed=0, lat_n=200):
    rng = np.random.default_rng(seed)
    carry = eng.init_carry(cfg)
    S = carry.lat_samples_n.shape[0]
    n = np.zeros(S, np.float32)
    lat = np.zeros(S, np.float32)
    n[:lat_n] = rng.integers(1, 60, lat_n)
    lat[:lat_n] = 3e-4 + 6e-5 * n[:lat_n] * rng.uniform(0.8, 1.2, lat_n)
    return carry._replace(obs_counts=jnp.asarray(oc),
                          obs_rewards=jnp.asarray(orw),
                          lat_samples_n=jnp.asarray(n),
                          lat_samples_l=jnp.asarray(lat),
                          lat_ptr=jnp.int32(lat_n))


@pytest.fixture(scope="module", params=sorted(SHAPES))
def deployment(request):
    specs = SHAPES[request.param]()
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=16, gather_stats=True, **COST)
    oc, orw = _observations(specs, cfg.max_states, seed=3)
    return specs, cfg, _carry(cfg, oc, orw)


def _per_query(specs, cfg, carry, width):
    """The per-query definition, one query at a time: (tables, bins,
    chains)."""
    oc, orw = np.asarray(carry.obs_counts), np.asarray(carry.obs_rewards)
    tables, Ts = [], []
    for p, s in enumerate(specs):
        m = s.num_states
        stats = markov.TransitionStats(counts=jnp.asarray(oc[p, :m, :m]),
                                       reward_sum=jnp.asarray(orw[p, :m, :m]))
        T = markov.estimate_transition_matrix(stats)
        R = markov.estimate_reward_matrix(
            stats, default_reward=cfg.c_match * float(s.proc_cost))
        Ts.append(np.asarray(T))
        tables.append(util.build_utility_table(
            T, R, window_size=s.window_size, bin_size=BIN, weight=s.weight))
    ut, bins = util.stack_tables(tables, max_states=cfg.max_states)
    ut = np.asarray(ut)
    if ut.shape[1] < width:
        ut = np.pad(ut, ((0, 0), (0, width - ut.shape[1]), (0, 0)))
    return ut[:, :width], np.asarray(bins), Ts


def _fit(specs, cfg, carry, width=None):
    return runner.fit_model(specs, cfg, carry, bin_size=BIN, width=width)


class TestFitMatchesPerQuery:
    def test_tables_chains_and_latency(self, deployment):
        specs, cfg, carry = deployment
        fit = _fit(specs, cfg, carry)
        width = RT.table_width(specs, BIN)
        ut, bins, Ts = _per_query(specs, cfg, carry, width)
        assert fit.ut_tables.shape == ut.shape == (len(specs), width,
                                                   cfg.max_states)
        np.testing.assert_allclose(np.asarray(fit.ut_tables), ut,
                                   rtol=1e-6, atol=0)
        np.testing.assert_array_equal(np.asarray(fit.ut_bins), bins)
        T = np.asarray(fit.T)
        for p, Tp in enumerate(Ts):
            m = Tp.shape[0]
            np.testing.assert_allclose(T[p, :m, :m], Tp, rtol=1e-6, atol=0)
            assert not T[p, m:].any() and not T[p, :, m:].any()
            # The row with no observations is a self-loop.
            empty = np.flatnonzero(
                np.asarray(carry.obs_counts)[p, :m - 1, :m].sum(1) == 0)
            assert empty.size and all(T[p, i, i] == 1.0 for i in empty)
        f = ovl.fit_latency_model(
            carry.lat_samples_n, carry.lat_samples_l,
            jnp.arange(carry.lat_samples_n.shape[0]) < carry.lat_ptr)
        for got, want in ((fit.f_model.a, f.a), (fit.f_model.b, f.b)):
            np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
        assert int(fit.f_model.kind) == int(f.kind)
        assert bool(fit.tables_finite) and bool(fit.f_finite)

    @pytest.mark.parametrize("extra", [2, -1])
    def test_zero_beyond_bins_and_states_at_width(self, deployment, extra):
        """Wider than the widest query: zero bins appended; narrower: cut,
        as ``_fit`` padded or sliced ``stack_tables``' result."""
        specs, cfg, carry = deployment
        width = RT.table_width(specs, BIN) + extra
        got = np.asarray(_fit(specs, cfg, carry, width).ut_tables)
        assert got.shape == (len(specs), width, cfg.max_states)
        for p, s in enumerate(specs):
            nb = -(-s.window_size // BIN)
            assert not got[p, nb:].any() and not got[p, :, s.num_states:].any()
            assert (got[p, :nb, :s.num_states] > 0).all()
        ut, _, _ = _per_query(specs, cfg, carry, width)
        np.testing.assert_allclose(got, ut, rtol=1e-6, atol=0)


@contextlib.contextmanager
def _dispatches(monkeypatch):
    """Names of every dispatch made from Python: each jitted call (its C++
    fast path turned off, so that every call takes pjit's Python path) and
    each primitive applied op by op (``dispatch.apply_primitive``)."""
    calls = []
    run, prim = pjit._run_python_pjit, dispatch.xla_primitive_callable

    def counting_run(p, args_flat, fun, *rest):
        calls.append(getattr(fun, "__name__", "?"))
        return run(p, args_flat, fun, *rest)

    def counting_prim(primitive, **params):
        calls.append(primitive.name)
        return prim(primitive, **params)
    jax.clear_caches()           # drop the fast paths already built
    monkeypatch.setattr(pjit, "_get_fastpath_data", lambda *a, **k: None)
    monkeypatch.setattr(pjit, "_run_python_pjit", counting_run)
    monkeypatch.setattr(dispatch, "xla_primitive_callable", counting_prim)
    try:
        yield calls
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def _refresh(specs, cfg, carry, state, rcfg=None, model=None):
    rcfg = rcfg or RT.RefreshConfig(min_observations=1.0)
    if model is None:
        model = RT.prepare_model(
            specs, eng.make_model(pat.compile_patterns(specs), cfg), rcfg)
    return RT.refresh_model(specs, cfg, model, carry, rcfg, state,
                            RT.TelemetryLog()), model


class TestRefreshDecisions:
    def test_refresh_deploys_the_fit(self, deployment):
        specs, cfg, carry = deployment
        state = RT.RefreshState()
        (m2, _, did), m0 = _refresh(specs, cfg, carry, state)
        fit = _fit(specs, cfg, carry, m0.ut_tables.shape[1])
        assert did and state.refresh_count == 1
        assert m2.ut_tables.shape == m0.ut_tables.shape
        np.testing.assert_array_equal(np.asarray(m2.ut_tables),
                                      np.asarray(fit.ut_tables))
        np.testing.assert_array_equal(state.last_T, np.asarray(fit.T))
        assert state.last_T.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(m2.f_model.a),
                                      np.asarray(fit.f_model.a))

    def test_nan_observations_skip(self, deployment):
        specs, cfg, carry = deployment
        state = RT.RefreshState()
        bad = carry._replace(obs_counts=carry.obs_counts.at[0, 0, 1].set(
            jnp.nan))
        (m2, _, did), m0 = _refresh(specs, cfg, bad, state)
        assert not did and m2 is m0
        assert state.skipped_nonfinite == 1 and state.refresh_count == 0

    def test_nonfinite_tables_skip(self, deployment):
        """Finite observations whose tables overflow (a weight of 1e33 over
        the remaining-time floor of 1e-6): the per-query path builds
        non-finite tables too, and the refresh is skipped."""
        specs, cfg, carry = deployment
        specs = [dataclasses.replace(specs[0], weight=1e33)] + specs[1:]
        ut, _, _ = _per_query(specs, cfg, carry, RT.table_width(specs, BIN))
        assert not np.isfinite(ut).all()
        assert not bool(_fit(specs, cfg, carry).tables_finite)
        state = RT.RefreshState()
        (m2, _, did), m0 = _refresh(specs, cfg, carry, state)
        assert not did and m2 is m0 and state.last_T is None
        assert state.skipped_nonfinite == 1 and state.refresh_count == 0

    def test_inf_latency_ring_keeps_f(self, deployment):
        """A non-finite f keeps the deployed f, counts, and still deploys
        the tables."""
        specs, cfg, carry = deployment
        bad = carry._replace(lat_samples_l=carry.lat_samples_l.at[3].set(
            jnp.inf))
        state = RT.RefreshState()
        (m2, _, did), m0 = _refresh(specs, cfg, bad, state)
        assert did and state.refresh_count == 1
        assert state.skipped_nonfinite == 1
        assert m2.f_model is m0.f_model
        np.testing.assert_array_equal(
            np.asarray(m2.ut_tables),
            np.asarray(_fit(specs, cfg, carry,
                            m0.ut_tables.shape[1]).ut_tables))

    def test_drift_gate(self, deployment):
        """A stable stream skips the rebuild; a drifted one refreshes."""
        specs, cfg, carry = deployment
        rcfg = RT.RefreshConfig(min_observations=1.0, drift_threshold=1e-6)
        state = RT.RefreshState()
        (m1, _, did1), _ = _refresh(specs, cfg, carry, state, rcfg)
        (_, _, did2), _ = _refresh(specs, cfg, carry, state, rcfg, m1)
        assert did1 and not did2 and state.skipped_drift == 1
        oc, orw = _observations(specs, cfg.max_states, seed=11)
        (_, _, did3), _ = _refresh(specs, cfg, _carry(cfg, oc, orw), state,
                                   rcfg, m1)
        assert did3 and state.refresh_count == 2 and state.skipped_drift == 1


class TestOneProgram:
    def test_second_refresh_does_not_retrace(self, deployment):
        specs, cfg, carry = deployment
        state = RT.RefreshState()
        (m1, _, _), _ = _refresh(specs, cfg, carry, state)
        oc, orw = _observations(specs, cfg.max_states, seed=5)
        with tracing.CompileCounter(util.fit_model) as cc:
            (_, _, did), _ = _refresh(specs, cfg, _carry(cfg, oc, orw, 1),
                                      state, model=m1)
        assert did
        assert cc.compiles(util.fit_model) == 0
        assert cc.traces("utility.fit_model") == 0

    def test_refresh_is_one_dispatch(self, deployment, monkeypatch):
        """After warm-up a refresh is one compiled call: no op runs eagerly.
        The per-query path it replaced dispatches dozens."""
        specs, cfg, carry = deployment
        state = RT.RefreshState()
        with _dispatches(monkeypatch) as calls:
            (m1, _, _), _ = _refresh(specs, cfg, carry, state)   # warm-up
            _per_query(specs, cfg, carry, RT.table_width(specs, BIN))
            calls.clear()
            _per_query(specs, cfg, carry, RT.table_width(specs, BIN))
            assert len(calls) > 10 * len(specs)
            calls.clear()
            (_, _, did), _ = _refresh(specs, cfg, carry, state, model=m1)
            assert did and calls == ["fit_model"]

    def test_build_model_compiles_the_refresh_program(self):
        """``build_model`` fits with the refresh's own program, so the
        first refresh of the served runtime compiles nothing."""
        specs = [pat.make_q1(window_size=w, num_symbols=4)
                 for w in (300, 600)]
        cp = pat.compile_patterns(specs)
        cfg = runner.default_config(cp, max_pms=16, gather_stats=True,
                                    **COST)
        raw = streams.gen_stock(600, num_symbols=20, pattern_symbols=4,
                                p_class=0.2, seed=1)
        warm = streams.classify(specs, raw, rate=100.0, seed=0)
        built = runner.build_model(specs, cfg, warm, bin_size=BIN)
        assert built.ut_stacked.shape == (2, RT.table_width(specs, BIN),
                                          cp.max_states)
        model = eng.make_model(cp, cfg, ut_tables=built.ut_stacked,
                               ut_bins=built.ut_bins, f_model=built.f_model,
                               g_model=built.g_model)
        carry, _ = eng.run_engine(cfg, model, warm, eng.init_carry(cfg))
        with tracing.CompileCounter(util.fit_model) as cc:
            (_, _, did), _ = _refresh(specs, cfg, carry, RT.RefreshState(),
                                      RT.RefreshConfig(bin_size=BIN,
                                                       min_observations=1.0),
                                      model)
        assert did and cc.traces("utility.fit_model") == 0
        assert cc.compiles(util.fit_model) == 0
