"""Pallas TPU flash attention (online softmax, VMEM-resident accumulators).

The pure-jnp flash in repro/models/layers.py spills its (cq × ck) f32 score
blocks to HBM — the roofline baselines show that traffic DOMINATING the
memory term of the prefill/train cells.  This kernel keeps scores, the
running max/denominator, and the output accumulator in VMEM scratch across
the kv-block loop; HBM sees only Q/K/V reads and one O write.

Grid: (B·H, nq, nk) — the kv axis is the innermost (sequential) dimension so
the scratch carries across j.  Causal blocks above the diagonal are skipped
via pl.when (no MXU work issued).

TARGET: TPU (MXU-aligned cq/ck multiples of 128, f32 scratch).
Checked interpreted on CPU against ref.attention_ref (tests/); not
compiled for a TPU by tests/test_tpu_compile.py (no CEP cell uses it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, cq: int, ck: int, nk: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _block():
        q = q_ref[0].astype(jnp.float32)              # (cq, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (ck, d)
        v = v_ref[0, 0].astype(jnp.float32)           # (ck, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (cq, ck)
        if causal:
            qpos = i * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
            kpos = j * ck + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]                           # (cq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:
        pl.when((i + 1) * cq - 1 >= j * ck)(_block)
    else:
        _block()

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "cq", "ck",
                                             "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, cq: int = 128, ck: int = 128,
                           interpret: bool) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Sk, KVH, D).  GQA via KVH | H.

    ``interpret=True`` runs the kernel body through the Pallas interpreter
    (any backend); ``interpret=False`` compiles it for a TPU.
    """
    B, Sq, H, D = q.shape
    _, Sk, KVH, Dv = v.shape
    G = H // KVH
    scale = 1.0 / np.sqrt(D)
    cq = min(cq, Sq)
    ck = min(ck, Sk)
    assert Sq % cq == 0 and Sk % ck == 0
    nq, nk = Sq // cq, Sk // ck

    # Layout: (B·H, S, D) with KV heads group-expanded via the index map
    # (no materialized repeat).
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kh = k.transpose(0, 2, 1, 3)                      # (B, KVH, Sk, D)
    vh = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               cq=cq, ck=ck, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, cq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, ck, D),
                         lambda b, i, j, G=G, H=H: (b // H, (b % H) // G,
                                                    j, 0)),
            pl.BlockSpec((1, 1, ck, Dv),
                         lambda b, i, j, G=G, H=H: (b // H, (b % H) // G,
                                                    j, 0)),
        ],
        out_specs=pl.BlockSpec((1, cq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((cq, 1), jnp.float32),
            pltpu.VMEM((cq, 1), jnp.float32),
            pltpu.VMEM((cq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)
