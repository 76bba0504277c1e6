"""Markov-chain / Markov-reward-process machinery for pSPICE (paper §III-C).

The pattern-matching state machine of a query q is modeled as a Markov chain
over states S_q = {s_1 .. s_m} (s_1 = initial, s_m = final/absorbing).  The
transition matrix T_q is estimated online from ``Observation<q, s, s', t>``
tuples emitted by the CEP operator; t is the measured processing time of that
transition and becomes the reward of a Markov reward process (MRP).

Everything here is pure JAX so model (re)building can run jitted on-device —
the paper's "model builder" component (§III-A).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


# ---------------------------------------------------------------------------
# Observation accumulation (statistic gathering, §III-C-1)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TransitionStats:
    """Scatter-add accumulator for transition counts and reward sums.

    counts[s, s']      — number of observed s -> s' transitions
    reward_sum[s, s']  — summed processing time of those transitions
    """
    counts: Array      # (m, m) float32
    reward_sum: Array  # (m, m) float32

    @staticmethod
    def zeros(m: int) -> "TransitionStats":
        return TransitionStats(
            counts=jnp.zeros((m, m), jnp.float32),
            reward_sum=jnp.zeros((m, m), jnp.float32),
        )

    @property
    def num_states(self) -> int:
        return self.counts.shape[0]

    @property
    def num_observations(self) -> Array:
        return self.counts.sum()


jax.tree_util.register_pytree_node(
    TransitionStats,
    lambda ts: ((ts.counts, ts.reward_sum), None),
    lambda _, ch: TransitionStats(*ch),
)


@jax.jit
def add_observations(stats: TransitionStats, s: Array, s_next: Array,
                     t: Array, valid: Array) -> TransitionStats:
    """Batched scatter-add of observations <s, s', t> (masked by ``valid``).

    s, s_next: int32 (n,) state indices; t: float32 (n,) processing times.
    """
    w = valid.astype(jnp.float32)
    counts = stats.counts.at[s, s_next].add(w)
    rsum = stats.reward_sum.at[s, s_next].add(w * t)
    return TransitionStats(counts, rsum)


# ---------------------------------------------------------------------------
# Transition matrix & reward function (§III-C-1/2)
# ---------------------------------------------------------------------------

def estimate_transition_matrix(stats: TransitionStats,
                               absorbing_final: bool = True,
                               laplace: float = 0.0) -> Array:
    """Row-normalized transition matrix T[s, s'] from counts.

    Rows with zero observations become self-loops (the chain stays put — the
    conservative prior for an unseen state).  The final state is absorbing:
    once a PM completes, it stays completed (paper Fig. 4's last row).
    """
    m = stats.num_states
    c = stats.counts + laplace
    row = c.sum(axis=1, keepdims=True)
    T = jnp.where(row > 0, c / jnp.maximum(row, 1e-30), jnp.eye(m))
    if absorbing_final:
        T = T.at[m - 1].set(jax.nn.one_hot(m - 1, m))
    return T


def estimate_reward_matrix(stats: TransitionStats,
                           default_reward: float = 0.0) -> Array:
    """R[s, s'] = mean observed processing time of an s -> s' transition."""
    c = stats.counts
    return jnp.where(c > 0, stats.reward_sum / jnp.maximum(c, 1e-30),
                     default_reward)


def estimate_chains(counts: Array, reward_sum: Array, num_states,
                    default_reward) -> tuple[Array, Array]:
    """``estimate_transition_matrix`` and ``estimate_reward_matrix`` for P
    queries at once, from (P, M, M) accumulators padded to M states.

    ``num_states`` (P,) and ``default_reward`` (P,) are host constants.
    Query p's states from ``num_states[p]`` on are padding: no observation
    there counts, their rows are self-loops and no live state moves to
    them, so every T is block-diagonal and its live block (absorbing in
    the query's own final state ``num_states[p] - 1``) evolves alone.
    """
    M = counts.shape[-1]
    m = np.asarray(num_states)[:, None, None]
    idx = np.arange(M)
    live = (idx[:, None] < m) & (idx[None, :] < m)          # (P, M, M)
    c = jnp.where(live, counts, 0.0)
    row = c.sum(axis=2, keepdims=True)
    eye = jnp.eye(M, dtype=c.dtype)
    T = jnp.where(row > 0, c / jnp.maximum(row, 1e-30), eye)
    T = jnp.where(idx[:, None] == m - 1, eye, T)            # absorbing final
    dr = np.asarray(default_reward, np.float32)[:, None, None]
    R = jnp.where(c > 0, jnp.where(live, reward_sum, 0.0)
                  / jnp.maximum(c, 1e-30), dr)
    return T, R


# ---------------------------------------------------------------------------
# Completion probability  P_pm = T^{R_w}(i, m)   (paper Eq. 3)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_bins", "bin_size"))
def binned_matrix_powers(T: Array, num_bins: int, bin_size: int) -> Array:
    """Return stacked powers  [T^{bs}, T^{2·bs}, ..., T^{num_bins·bs}].

    The paper computes T^{R_w} only at every ``bs`` events to bound memory
    (§III-C-1) and interpolates between bins.  Computed as a scan of m×m
    matmuls (MXU-friendly), each at full float32 precision (``_mm``).
    """
    T_bs = _matrix_power(T, bin_size)

    def step(acc, _):
        acc = _mm(acc, T_bs)
        return acc, acc

    eye = jnp.eye(T.shape[0], dtype=T.dtype)
    _, powers = jax.lax.scan(step, eye, None, length=num_bins)
    return powers  # (num_bins, m, m)


def _mm(a: Array, b: Array) -> Array:
    """``a @ b`` at full float32 precision.  A TPU's default precision
    rounds float32 operands to bfloat16: a chain's stay probabilities near
    1 then lose their third digit, and the thousand-step powers and value
    iterations below drift by tens of percent."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _matrix_power(T: Array, k: int) -> Array:
    """T^k by binary exponentiation (k is a static Python int)."""
    result = jnp.eye(T.shape[0], dtype=T.dtype)
    base = T
    while k > 0:
        if k & 1:
            result = _mm(result, base)
        base = _mm(base, base)
        k >>= 1
    return result


def completion_probability_table(T: Array, num_bins: int,
                                 bin_size: int) -> Array:
    """P[j, i] = prob. a PM in state s_i completes given (j+1)·bs events left.

    The last column of T^{R_w} (paper Fig. 4's red box).
    """
    powers = binned_matrix_powers(T, num_bins, bin_size)
    return powers[:, :, -1]  # (num_bins, m)


def completion_probability_tables(T: Array, num_states, num_bins: int,
                                  bin_size: int) -> Array:
    """``completion_probability_table`` for P stacked chains
    (``estimate_chains``): the column of each query's own final state
    ``num_states[p] - 1``, from one batched run of the binned powers;
    (P, num_bins, M)."""
    powers = jax.vmap(lambda T1: binned_matrix_powers(
        T1, num_bins, bin_size))(T)
    final = np.asarray(num_states) - 1
    return powers[np.arange(T.shape[0]), :, :, final]


# ---------------------------------------------------------------------------
# Remaining processing time via MRP value iteration  (§III-C-2)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("num_bins", "bin_size"))
def remaining_time_table(T: Array, R: Array, num_bins: int,
                         bin_size: int) -> Array:
    """tau[j, i] = expected remaining processing time of a PM in state s_i
    given (j+1)·bs events remain in its window.

    Bellman backup (value iteration, Howard'71):
        tau_{k}(s) = sum_{s'} T[s,s'] · (R[s,s'] + tau_{k-1}(s'))
    with the final state absorbing at zero cost (a completed PM consumes no
    further processing).  Iteration index k == events remaining R_w; we keep
    every bin_size-th iterate (paper keeps results per bin, interpolates).
    """
    m = T.shape[0]
    # Expected one-step reward per state: r(s) = sum_s' T[s,s']·R[s,s'].
    r = (T * R).sum(axis=1).at[m - 1].set(0.0)
    T_nofinal = T.at[m - 1].set(0.0)  # absorbing final contributes 0 onward
    return _value_iteration(r, T_nofinal, num_bins, bin_size)


def remaining_time_tables(T: Array, R: Array, num_states, num_bins: int,
                          bin_size: int) -> Array:
    """``remaining_time_table`` for P stacked chains (``estimate_chains``):
    each query's final state is its own ``num_states[p] - 1``, and it and
    the padding beyond cost nothing onward.  One value iteration over all
    P queries; (P, num_bins, M)."""
    M = T.shape[-1]
    done = np.arange(M)[None, :] >= np.asarray(num_states)[:, None] - 1
    r = jnp.where(done, 0.0, (T * R).sum(axis=2))
    T_nofinal = jnp.where(done[:, :, None], 0.0, T)
    return jax.vmap(lambda r1, T1: _value_iteration(
        r1, T1, num_bins, bin_size))(r, T_nofinal)


def _value_iteration(r: Array, T_nofinal: Array, num_bins: int,
                     bin_size: int) -> Array:
    """Every ``bin_size``-th iterate of tau <- r + T_nofinal @ tau."""
    def one_event(tau, _):
        tau = r + _mm(T_nofinal, tau)
        return tau, None

    def one_bin(tau, _):
        tau, _ = jax.lax.scan(one_event, tau, None, length=bin_size)
        return tau, tau

    tau0 = jnp.zeros(r.shape, r.dtype)
    _, taus = jax.lax.scan(one_bin, tau0, None, length=num_bins)
    return taus  # (num_bins, m)


# ---------------------------------------------------------------------------
# Drift detection for retraining (§III-D)
# ---------------------------------------------------------------------------

def transition_matrix_mse(T_model: Array, T_fresh: Array) -> Array:
    """Mean squared error between the deployed and freshly-estimated matrix."""
    return jnp.mean((T_model - T_fresh) ** 2)


def needs_retraining(T_model: Array, T_fresh: Array,
                     threshold: float = 1e-3) -> Array:
    return transition_matrix_mse(T_model, T_fresh) > threshold


# ---------------------------------------------------------------------------
# NumPy reference oracles (used by tests)
# ---------------------------------------------------------------------------

def np_completion_probability(T: np.ndarray, R_w: int) -> np.ndarray:
    """Oracle: last column of T^R_w."""
    return np.linalg.matrix_power(np.asarray(T, np.float64), R_w)[:, -1]


def np_remaining_time(T: np.ndarray, R: np.ndarray, R_w: int) -> np.ndarray:
    """Oracle: naive value iteration in float64."""
    T = np.asarray(T, np.float64).copy()
    R = np.asarray(R, np.float64)
    m = T.shape[0]
    r = (T * R).sum(axis=1)
    r[m - 1] = 0.0
    Tn = T.copy()
    Tn[m - 1] = 0.0
    tau = np.zeros(m)
    for _ in range(R_w):
        tau = r + Tn @ tau
    return tau
