"""Tests for the load shedders (paper §III-F / §IV-A baselines)."""
import jax
import jax.numpy as jnp
import numpy as np

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # declared in requirements-dev.txt; deterministic
    from _hyp_fallback import given, settings, st  # fallback sweeps

from repro.core import shedder


class TestDropLowestUtility:
    @given(st.integers(0, 64), st.integers(1, 64))
    @settings(max_examples=30, deadline=None)
    def test_drops_exactly_rho_of_active(self, rho, n_active):
        N = 64
        rng = np.random.default_rng(rho * 97 + n_active)
        active = np.zeros(N, bool)
        active[rng.choice(N, n_active, replace=False)] = True
        u = jnp.asarray(rng.random(N), jnp.float32)
        u = jnp.where(jnp.asarray(active), u, jnp.inf)
        new = shedder.drop_lowest_utility(jnp.asarray(active), u,
                                          jnp.int32(rho))
        dropped = n_active - int(new.sum())
        assert dropped == min(rho, n_active)

    def test_drops_the_lowest(self):
        active = jnp.ones(6, bool)
        u = jnp.array([5., 1., 3., 0.5, 2., 4.])
        new = shedder.drop_lowest_utility(active, u, jnp.int32(3))
        np.testing.assert_array_equal(
            np.asarray(new), [True, False, True, False, False, True])

    def test_never_revives_inactive(self):
        active = jnp.array([False, True, False, True])
        u = jnp.where(active, jnp.array([1., 2., 3., 4.]), jnp.inf)
        new = shedder.drop_lowest_utility(active, u, jnp.int32(1))
        assert not bool(new[0]) and not bool(new[2])


class TestThresholdDropMask:
    """The O(N) histogram-refinement select vs the argsort oracle."""

    @given(st.integers(0, 500), st.integers(0, 2000))
    @settings(max_examples=40, deadline=None)
    def test_matches_oracle_invariants(self, seed, rho):
        rng = np.random.default_rng(seed)
        N = int(rng.integers(3, 400))
        active = np.asarray(rng.random(N) < rng.uniform(0.1, 1.0))
        scale = float(10 ** rng.uniform(-2, 3))
        if seed % 3 == 0:  # tie-heavy: a handful of distinct levels
            levels = np.linspace(0, scale, int(rng.integers(1, 5)))
            u = rng.choice(levels, N).astype(np.float32)
        else:
            u = (rng.random(N) * scale).astype(np.float32)
        u_act = jnp.where(jnp.asarray(active), jnp.asarray(u), jnp.inf)
        new = shedder.threshold_drop_mask(jnp.asarray(active), u_act,
                                          jnp.int32(rho))
        oracle = shedder.drop_lowest_utility(jnp.asarray(active), u_act,
                                             jnp.int32(rho))
        n_active = int(active.sum())
        # Exactly the oracle's count...
        assert int(new.sum()) == int(oracle.sum())
        assert n_active - int(new.sum()) == min(rho, n_active)
        # ...never revives inactive slots...
        assert not bool(jnp.any(new & ~jnp.asarray(active)))
        # ...and respects the threshold up to the final bucket width.
        dropped = active & ~np.asarray(new)
        kept = np.asarray(new)
        if dropped.any() and kept.any():
            span = u[active].max() - u[active].min()
            tol = max(span / 128.0 ** 3, 1e-6)
            assert u[dropped].max() <= u[kept].min() + tol * 1.01

    @given(st.integers(0, 500), st.integers(0, 2000))
    @settings(max_examples=25, deadline=None)
    def test_rows_form_bitwise_equals_flat(self, seed, rho):
        """The block kernel's (R, N) restatement drops exactly the flat
        select's slots (row-major order), ties and all."""
        rng = np.random.default_rng(seed)
        R, N = int(rng.integers(1, 4)), int(rng.integers(1, 150))
        active = rng.random((R, N)) < rng.uniform(0.1, 1.0)
        if seed % 3 == 0:
            u = rng.integers(0, 4, (R, N)).astype(np.float32)
        else:
            u = (rng.random((R, N)) * 10 ** rng.uniform(-2, 3)).astype(
                np.float32)
        flat = jax.jit(shedder.threshold_drop_mask)(
            jnp.asarray(active.reshape(-1)), jnp.asarray(u.reshape(-1)),
            jnp.int32(rho))
        rows = jax.jit(shedder.threshold_drop_mask_rows)(
            jnp.asarray(active), jnp.asarray(u), jnp.int32(rho))
        np.testing.assert_array_equal(np.asarray(rows).reshape(-1),
                                      np.asarray(flat))

    def test_all_ties_bitwise_equals_oracle(self):
        """Once every candidate holds one f32 value, the index tie-break
        IS the stable argsort order — bitwise equality."""
        active = jnp.ones(200, bool)
        u = jnp.full((200,), 0.5, jnp.float32)
        for rho in (0, 1, 50, 199, 200, 999):
            np.testing.assert_array_equal(
                np.asarray(shedder.threshold_drop_mask(active, u,
                                                       jnp.int32(rho))),
                np.asarray(shedder.drop_lowest_utility(active, u,
                                                       jnp.int32(rho))))

    def test_shed_dispatch_plans_agree_on_count(self):
        rng = np.random.default_rng(5)
        N = 256
        active = jnp.asarray(rng.random(N) < 0.8)
        tables = jnp.asarray(rng.random((2, 8, 4)), jnp.float32)
        bins = jnp.array([32, 32], jnp.int32)
        pid = jnp.asarray(rng.integers(0, 2, N), jnp.int32)
        state = jnp.asarray(rng.integers(0, 4, N), jnp.int32)
        r_w = jnp.asarray(rng.integers(1, 256, N), jnp.int32)
        key = jax.random.PRNGKey(0)
        kw = dict(key=key, active=active, rho=jnp.int32(37),
                  stacked_tables=tables, bin_sizes=bins, pattern_id=pid,
                  state=state, r_w=r_w)
        a = shedder.shed("pspice", plan="threshold", **kw)
        b = shedder.shed("pspice", plan="sort", **kw)
        assert int(a.sum()) == int(b.sum())


class TestRandomDrop:
    def test_exact_budget(self):
        key = jax.random.PRNGKey(0)
        active = jnp.ones(128, bool)
        new = shedder.random_drop(key, active, jnp.int32(40))
        assert int(new.sum()) == 88

    def test_uniformity(self):
        """Each active PM should be dropped with ~equal frequency."""
        active = jnp.ones(16, bool)
        counts = np.zeros(16)
        for i in range(300):
            new = shedder.random_drop(jax.random.PRNGKey(i), active,
                                      jnp.int32(4))
            counts += ~np.asarray(new)
        freq = counts / 300
        assert abs(freq.mean() - 0.25) < 0.01
        assert freq.std() < 0.06


class TestEBL:
    def test_irrelevant_types_shed_first(self):
        pattern_class = jnp.array([0, 1, 2, 0], jnp.int32)  # types 0,3 irrel
        rep = jnp.array([0.0, 1.0, 2.0])
        freq = jnp.array([0.4, 0.1, 0.1, 0.4])
        u = shedder.ebl_type_utilities(pattern_class, rep, freq)
        assert float(u[0]) == 0.0 and float(u[3]) == 0.0
        assert float(u[2]) > float(u[1]) > 0

    def test_drop_mask_respects_budget(self):
        key = jax.random.PRNGKey(1)
        types = jnp.zeros(10000, jnp.int32)
        utils = jnp.array([0.0])
        mask = shedder.ebl_drop_mask(key, types, utils, jnp.float32(0.3))
        assert abs(float(mask.mean()) - 0.3) < 0.05
