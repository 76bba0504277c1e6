"""Unit + property tests for the pSPICE Markov machinery (paper §III-C)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # declared in requirements-dev.txt; deterministic
    from _hyp_fallback import given, settings, st  # fallback sweeps

from repro.core import markov, overload, utility


def _random_stats(rng, m):
    stats = markov.TransitionStats.zeros(m)
    n = 200
    s = jnp.asarray(rng.integers(0, m, n), jnp.int32)
    sn = jnp.asarray(np.minimum(s + rng.integers(0, 2, n), m - 1), jnp.int32)
    t = jnp.asarray(rng.random(n), jnp.float32)
    return markov.add_observations(stats, s, sn, t, jnp.ones(n, bool))


class TestTransitionMatrix:
    def test_rows_are_distributions(self):
        stats = _random_stats(np.random.default_rng(0), 5)
        T = markov.estimate_transition_matrix(stats)
        np.testing.assert_allclose(np.asarray(T.sum(1)), 1.0, atol=1e-5)
        assert (np.asarray(T) >= 0).all()

    def test_final_state_absorbing(self):
        stats = _random_stats(np.random.default_rng(1), 4)
        T = markov.estimate_transition_matrix(stats)
        np.testing.assert_allclose(np.asarray(T[-1]),
                                   [0, 0, 0, 1], atol=1e-6)

    def test_unseen_state_self_loops(self):
        stats = markov.TransitionStats.zeros(3)
        stats = markov.add_observations(
            stats, jnp.array([0]), jnp.array([1]), jnp.array([1.0]),
            jnp.array([True]))
        T = markov.estimate_transition_matrix(stats)
        assert float(T[1, 1]) == 1.0  # state 1 never observed

    def test_masked_observations_ignored(self):
        stats = markov.TransitionStats.zeros(3)
        stats = markov.add_observations(
            stats, jnp.array([0, 0]), jnp.array([1, 2]),
            jnp.array([1.0, 1.0]), jnp.array([True, False]))
        assert float(stats.counts[0, 2]) == 0.0


class TestCompletionProbability:
    @given(st.integers(2, 6), st.integers(1, 5), st.integers(1, 4))
    @settings(max_examples=20, deadline=None)
    def test_matches_matrix_power_oracle(self, m, num_bins, bin_size):
        rng = np.random.default_rng(m * 100 + num_bins)
        T = rng.random((m, m))
        T /= T.sum(1, keepdims=True)
        P = markov.completion_probability_table(jnp.asarray(T, jnp.float32),
                                                num_bins, bin_size)
        for j in range(num_bins):
            oracle = markov.np_completion_probability(T, (j + 1) * bin_size)
            np.testing.assert_allclose(np.asarray(P[j]), oracle, atol=2e-4)

    def test_monotone_in_horizon_with_absorbing_final(self):
        # With an absorbing final state, completion prob can only grow with
        # the number of remaining events.
        stats = _random_stats(np.random.default_rng(2), 5)
        T = markov.estimate_transition_matrix(stats)
        P = markov.completion_probability_table(T, 8, 2)
        assert bool(jnp.all(P[1:] >= P[:-1] - 1e-6))


class TestRemainingTime:
    @given(st.integers(2, 5), st.integers(1, 12))
    @settings(max_examples=20, deadline=None)
    def test_matches_value_iteration_oracle(self, m, rw):
        rng = np.random.default_rng(m * 31 + rw)
        T = rng.random((m, m))
        T /= T.sum(1, keepdims=True)
        T[-1] = 0
        T[-1, -1] = 1
        R = rng.random((m, m))
        tau = markov.remaining_time_table(jnp.asarray(T, jnp.float32),
                                          jnp.asarray(R, jnp.float32),
                                          num_bins=rw, bin_size=1)
        oracle = markov.np_remaining_time(T, R, rw)
        np.testing.assert_allclose(np.asarray(tau[-1]), oracle, rtol=1e-4,
                                   atol=1e-4)

    def test_completed_pm_needs_no_time(self):
        stats = _random_stats(np.random.default_rng(3), 4)
        T = markov.estimate_transition_matrix(stats)
        R = markov.estimate_reward_matrix(stats)
        tau = markov.remaining_time_table(T, R, 6, 4)
        np.testing.assert_allclose(np.asarray(tau[:, -1]), 0.0, atol=1e-6)

    def test_nonnegative_and_monotone(self):
        stats = _random_stats(np.random.default_rng(4), 4)
        T = markov.estimate_transition_matrix(stats)
        R = markov.estimate_reward_matrix(stats)
        tau = markov.remaining_time_table(T, R, 6, 4)
        assert (np.asarray(tau) >= -1e-6).all()
        assert bool(jnp.all(tau[1:] >= tau[:-1] - 1e-5))


class TestUtilityTable:
    def test_shape_and_lookup(self):
        stats = _random_stats(np.random.default_rng(5), 4)
        T = markov.estimate_transition_matrix(stats)
        R = markov.estimate_reward_matrix(stats)
        ut = utility.build_utility_table(T, R, window_size=64, bin_size=8,
                                         weight=2.0)
        assert ut.table.shape == (8, 4)
        u = utility.lookup_utility(ut.table, 8, jnp.array([1, 2]),
                                   jnp.array([8, 64]))
        assert u.shape == (2,) and bool(jnp.isfinite(u).all())

    def test_weight_scales_utility(self):
        stats = _random_stats(np.random.default_rng(6), 4)
        T = markov.estimate_transition_matrix(stats)
        R = markov.estimate_reward_matrix(stats)
        u1 = utility.build_utility_table(T, R, 32, 4, weight=1.0).table
        u3 = utility.build_utility_table(T, R, 32, 4, weight=3.0).table
        np.testing.assert_allclose(np.asarray(u3), 3 * np.asarray(u1),
                                   rtol=1e-5)

    def test_pspice_minus_ignores_time(self):
        """pSPICE-- (Fig. 8 ablation): utility independent of rewards."""
        stats = _random_stats(np.random.default_rng(7), 4)
        T = markov.estimate_transition_matrix(stats)
        R1 = markov.estimate_reward_matrix(stats)
        u_a = utility.build_utility_table(T, R1, 32, 4,
                                          use_remaining_time=False).table
        u_b = utility.build_utility_table(T, R1 * 17.0, 32, 4,
                                          use_remaining_time=False).table
        np.testing.assert_allclose(np.asarray(u_a), np.asarray(u_b),
                                   rtol=1e-5)

    @given(st.integers(1, 300))
    @settings(max_examples=25, deadline=None)
    def test_interpolation_between_bins(self, rw):
        table = jnp.asarray(np.random.default_rng(8).random((10, 4)),
                            jnp.float32)
        u = utility.lookup_utility(table, 32, jnp.array([2]),
                                   jnp.array([rw]))
        lo, hi = float(table[:, 2].min()), float(table[:, 2].max())
        assert lo - 1e-5 <= float(u[0]) <= hi + 1e-5


class TestRetraining:
    def test_drift_detection(self):
        stats = _random_stats(np.random.default_rng(9), 4)
        T = markov.estimate_transition_matrix(stats)
        assert not bool(markov.needs_retraining(T, T))
        T2 = jnp.roll(T, 1, axis=1)
        assert bool(markov.needs_retraining(T, T2))


class TestOverloadDetector:
    def test_fit_recovers_linear_model(self):
        n = jnp.arange(1, 500, dtype=jnp.float32)
        lat = 3e-4 * n + 0.01
        m = overload.fit_latency_model(n, lat)
        assert int(m.kind) == overload.LINEAR
        np.testing.assert_allclose(float(m.a), 3e-4, rtol=1e-3)

    def test_fit_prefers_nlogn_when_true(self):
        n = jnp.arange(1, 500, dtype=jnp.float32)
        lat = 1e-4 * n * jnp.log2(n + 1) + 0.01
        m = overload.fit_latency_model(n, lat)
        assert int(m.kind) == overload.NLOGN

    @given(st.floats(1.0, 1e4))
    @settings(max_examples=25, deadline=None)
    def test_inverse_roundtrip(self, n):
        for kind in (overload.LINEAR, overload.NLOGN):
            m = overload.LatencyModel(a=jnp.float32(2e-4),
                                      b=jnp.float32(0.01),
                                      kind=jnp.int32(kind))
            got = float(overload.invert_latency(
                m, overload.predict_latency(m, jnp.float32(n))))
            assert abs(got - n) / n < 1e-2

    def test_log2_accuracy(self):
        """The add/multiply/divide log2 the block kernel shares with the
        xla step: exact on powers of two, within 2.5 ulps elsewhere."""
        rng = np.random.default_rng(0)
        x = np.concatenate([
            np.arange(1, 4097, dtype=np.float32),
            (1 + rng.random(4096) * 10 ** rng.uniform(0, 9, 4096)
             ).astype(np.float32)])
        got = np.asarray(jax.jit(overload.log2)(x), np.float64)
        ref = np.log2(x.astype(np.float64))
        ulp = np.spacing(np.abs(ref).astype(np.float32)).astype(np.float64)
        nz = ref != 0
        assert (np.abs(got - ref)[nz] / ulp[nz]).max() <= 2.5
        assert got[0] == 0.0
        for k in range(31):
            assert float(overload.log2(jnp.float32(2.0 ** k))) == k

    def test_algorithm1_rho(self):
        """Alg. 1: rho drops exactly to the sustainable PM count."""
        f = overload.LatencyModel(a=jnp.float32(1e-3), b=jnp.float32(0.0),
                                  kind=jnp.int32(overload.LINEAR))
        g = overload.LatencyModel(a=jnp.float32(0.0), b=jnp.float32(0.1),
                                  kind=jnp.int32(overload.LINEAR))
        # l_q=0.4, n_pm=1000 → l_p=1.0, l_e+l_s=1.5 > LB=1.0
        dec = overload.detect_overload(f, g, jnp.float32(0.4),
                                       jnp.int32(1000), 1.0)
        assert bool(dec.shed)
        # l'_p = 1.0-0.4-0.1 = 0.5 → n' = 500 → rho = 500
        assert int(dec.rho) == 500

    def test_no_shed_when_under_bound(self):
        f = overload.LatencyModel(a=jnp.float32(1e-6), b=jnp.float32(0.0),
                                  kind=jnp.int32(overload.LINEAR))
        dec = overload.detect_overload(f, f, jnp.float32(0.0),
                                       jnp.int32(10), 1.0)
        assert not bool(dec.shed) and int(dec.rho) == 0
