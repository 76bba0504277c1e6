"""Overload detection & shed-amount computation (paper §III-E, Algorithm 1).

The overload detector estimates, per input event,
    l_e = l_q + l_p        (queueing + processing latency)
and triggers shedding when  l_e + l_s (+ b_s) > LB.

l_p = f(n_pm) and l_s = g(n_pm) are regressions learned online from
(n_pm, latency) samples; the paper "applies several regression models ... and
uses the one that results in lower error".  We fit a linear model and an
n·log2(n) model and keep the better one.  f must be invertible to compute
n'_pm = f^{-1}(l'_p) (Alg. 1 line 7); both candidates have closed-form
inverses.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

Array = jax.Array

LINEAR, NLOGN = 0, 1


@dataclasses.dataclass
class LatencyModel:
    """l = a·basis(n) + b with basis either n or n·log2(n+1)."""
    a: Array
    b: Array
    kind: Array  # int32 scalar: LINEAR or NLOGN

    def __call__(self, n_pm: Array) -> Array:
        return predict_latency(self, n_pm)


jax.tree_util.register_pytree_node(
    LatencyModel,
    lambda m: ((m.a, m.b, m.kind), None),
    lambda _, ch: LatencyModel(*ch),
)


LN2 = math.log(2.0)


def log2(x: Array) -> Array:
    """log2 of a positive normal f32, from its exponent bits and a short
    atanh series in add, multiply and divide.

    On a TPU, ``jnp.log2`` is rounded differently by XLA and by the
    kernel compiler (Mosaic); add, multiply and divide are not, so the
    block kernel's Algorithm-1 arithmetic stays bit-identical to the xla
    step for NLOGN models too.  Accurate to 2.5 f32 ulps: m in [√½, √2)
    gives |s| ≤ 0.172, and the first omitted term is below 4e-10.
    """
    f32, i32 = jnp.float32, jnp.int32
    x = jnp.asarray(x, f32)
    # The kernel compiler bitcasts vectors only: lift a scalar to (1, 1).
    v = x.reshape(1, 1) if x.ndim == 0 else x
    bits = jax.lax.bitcast_convert_type(v, i32)
    e = (bits >> 23) - 127
    m = jax.lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F800000, f32)
    big = m > 1.4142135
    m = jnp.where(big, m * 0.5, m)
    e = jnp.where(big, e + 1, e)
    s = (m - 1.0) / (m + 1.0)
    s2 = s * s
    p = ((((s2 * (1.0 / 9.0) + 1.0 / 7.0) * s2 + 1.0 / 5.0) * s2
          + 1.0 / 3.0) * s2 + 1.0)
    out = e.astype(f32) + (2.0 * s * p) * (1.0 / LN2)
    return out[0, 0] if x.ndim == 0 else out


def _basis(n: Array, kind: Array) -> Array:
    n = n.astype(jnp.float32)
    return jnp.where(kind == LINEAR, n, n * log2(n + 1.0))


def _lstsq_1d(x: Array, y: Array, w: Array) -> tuple[Array, Array]:
    """Weighted least squares for y = a·x + b (closed form, jit-safe)."""
    sw = jnp.maximum(w.sum(), 1e-30)
    mx = (w * x).sum() / sw
    my = (w * y).sum() / sw
    cov = (w * (x - mx) * (y - my)).sum()
    var = jnp.maximum((w * (x - mx) ** 2).sum(), 1e-30)
    a = cov / var
    b = my - a * mx
    return a, b


@jax.jit
def fit_latency_model(n_pm: Array, latency: Array,
                      valid: Array | None = None) -> LatencyModel:
    """Fit both candidate regressions, keep the lower-SSE one (paper §III-E)."""
    w = jnp.ones_like(latency) if valid is None else valid.astype(jnp.float32)

    def fit(kind):
        x = _basis(n_pm, jnp.int32(kind))
        a, b = _lstsq_1d(x, latency, w)
        a = jnp.maximum(a, 1e-12)  # latency must increase with n_pm
        sse = (w * (a * x + b - latency) ** 2).sum()
        return a, b, sse

    a0, b0, e0 = fit(LINEAR)
    a1, b1, e1 = fit(NLOGN)
    pick_lin = e0 <= e1
    return LatencyModel(
        a=jnp.where(pick_lin, a0, a1),
        b=jnp.where(pick_lin, b0, b1),
        kind=jnp.where(pick_lin, LINEAR, NLOGN).astype(jnp.int32),
    )


def predict_latency(model: LatencyModel, n_pm: Array) -> Array:
    return model.a * _basis(jnp.asarray(n_pm), model.kind) + model.b


def _invert_nlogn(t: Array) -> Array:
    """Solve n·log2(n+1) = t by 16 Newton steps (monotone, convex — a
    handful suffice).  A ``fori_loop``: the block kernel inlines this, and
    the TPU kernel compiler lowers a counted loop but not a scan."""
    def newton(_, n):
        fn = n * log2(n + 1.0) - t
        dfn = log2(n + 1.0) + n / ((n + 1.0) * LN2)
        return jnp.clip(n - fn / jnp.maximum(dfn, 1e-9), 0.0, 1e12)

    return jax.lax.fori_loop(0, 16, newton, jnp.maximum(t, 1.0))


def invert_latency(model: LatencyModel, l_target: Array) -> Array:
    """n'_pm = f^{-1}(l'_p)  (Alg. 1 line 7).

    Linear: n = (l-b)/a.  For n·log2(n+1): Newton iterations
    (``_invert_nlogn``).
    """
    t = jnp.maximum((l_target - model.b) / model.a, 0.0)
    return jnp.where(model.kind == LINEAR, t, _invert_nlogn(t))


def invert_latency_lazy(model: LatencyModel, l_target: Array) -> Array:
    """``invert_latency`` with the Newton iteration under a ``lax.cond``:
    bit-identical results (each branch is the exact expression the
    ``jnp.where`` in ``invert_latency`` selects), but a LINEAR model pays
    only the closed-form inverse at runtime.  The per-event Algorithm-1
    check inside the block-step kernel (kernels/block_step.py) uses this
    — under vmap (tenant lanes) the cond lowers back to a select, which
    is exactly ``invert_latency``'s cost."""
    t = jnp.maximum((l_target - model.b) / model.a, 0.0)
    return jax.lax.cond(model.kind == LINEAR, lambda t: t, _invert_nlogn, t)


@dataclasses.dataclass
class OverloadDecision:
    shed: Array   # bool — does l_e + l_s (+ b_s) exceed LB?
    rho: Array    # int32 — number of PMs to drop (0 if not shedding)
    l_e: Array    # estimated event latency (for telemetry / Fig. 7)


jax.tree_util.register_pytree_node(
    OverloadDecision,
    lambda d: ((d.shed, d.rho, d.l_e), None),
    lambda _, ch: OverloadDecision(*ch),
)


def detect_overload(f_model: LatencyModel, g_model: LatencyModel,
                    l_q: Array, n_pm: Array, latency_bound: float,
                    safety_buffer: float = 0.0,
                    lazy: bool = False) -> OverloadDecision:
    """Algorithm 1: decide whether to shed and how many PMs to drop.

    l'_p = LB - l_q - l_s;  n'_pm = f^{-1}(l'_p);  rho = n_pm - n'_pm.
    ``lazy`` routes the inversion through ``invert_latency_lazy`` — the
    same bits, but the Newton path only executes for NLOGN models (the
    block-step kernel runs this check once per event, in-loop).
    """
    n_pm_f = n_pm.astype(jnp.float32)
    l_p = predict_latency(f_model, n_pm_f)
    l_s = predict_latency(g_model, n_pm_f)
    l_e = l_q + l_p
    shed = l_e + l_s + safety_buffer > latency_bound
    l_p_new = jnp.maximum(latency_bound - l_q - l_s - safety_buffer, 0.0)
    invert = invert_latency_lazy if lazy else invert_latency
    # +eps guards float32 round-down at exact solutions (n' must not be
    # under-counted by one — that would over-shed every call).
    n_keep = jnp.floor(invert(f_model, l_p_new) + 1e-4).astype(jnp.int32)
    rho = jnp.where(shed, jnp.maximum(n_pm - n_keep, 0), 0).astype(jnp.int32)
    return OverloadDecision(shed=shed, rho=rho, l_e=l_e)
