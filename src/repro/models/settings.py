"""Trace-time model settings.

``analysis_mode`` reconfigures every structural scan for roofline analysis:
XLA's HLO cost analysis counts a while-loop body ONCE (it does not multiply
by trip count), so the roofline lowering unrolls all scans (layers, flash
pairs, SSD chunks, loss chunks) at two reduced depths and extrapolates
linearly — see launch/roofline.py.  The deploy lowering keeps rolled scans
(small HLO, honest memory_analysis).
"""
from __future__ import annotations

import contextlib
import contextvars

import jax

_UNROLL = contextvars.ContextVar("repro_unroll_scans", default=False)
_FLASH_Q = contextvars.ContextVar("repro_flash_q", default=512)
_FLASH_KV = contextvars.ContextVar("repro_flash_kv", default=1024)
_LOSS_CHUNK = contextvars.ContextVar("repro_loss_chunk", default=512)
# Parallelism scheme: "tp" (TP over "model" + optional FSDP over "data"),
# "fsdp" (pure FSDP: batch over ALL axes, params sharded over data×model,
# no tensor parallelism), "moe2d" (TP + experts sharded (E × d_ff) 2-D).
_SCHEME = contextvars.ContextVar("repro_scheme", default="tp")
# Flip attention activations to batch-over-(data×model) when heads don't
# divide the model axis (minitron/whisper §Perf optimization).
_ATTN_BATCH_FLIP = contextvars.ContextVar("repro_attn_flip", default=False)


def scheme() -> str:
    return _SCHEME.get()


def attn_batch_flip() -> bool:
    return _ATTN_BATCH_FLIP.get()


@contextlib.contextmanager
def use_scheme(name: str = "tp", attn_flip: bool = False):
    t1 = _SCHEME.set(name)
    t2 = _ATTN_BATCH_FLIP.set(attn_flip)
    try:
        yield
    finally:
        _SCHEME.reset(t1)
        _ATTN_BATCH_FLIP.reset(t2)


def unroll_scans() -> bool:
    return _UNROLL.get()


def flash_chunks() -> tuple[int, int]:
    return _FLASH_Q.get(), _FLASH_KV.get()


def loss_chunk() -> int:
    return _LOSS_CHUNK.get()


def scan(f, init, xs, length=None):
    """lax.scan honoring analysis-mode unrolling."""
    return jax.lax.scan(f, init, xs, length=length,
                        unroll=True if _UNROLL.get() else 1)


def constrain(x, *axes):
    """with_sharding_constraint by axis names, one entry per dim (None =
    replicated).  Silently no-ops outside a mesh context and drops axes that
    don't divide the dim — safe in unit tests and for odd batch sizes.

    Axis entries may be tuples (e.g. ("pod", "data")); "data" is auto-
    upgraded to ("pod", "data") when a pod axis exists in the mesh.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = set(mesh.axis_names)
    sch = scheme()
    spec = []
    for dim, ax in zip(x.shape, axes):
        if ax is None:
            spec.append(None)
            continue
        ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
        if sch == "fsdp":
            # pure-FSDP: no tensor axis; batch spreads over every axis.
            if ax_t == ("model",):
                spec.append(None)
                continue
            if "data" in ax_t and "model" not in ax_t:
                ax_t = ax_t + ("model",)
        if "data" in ax_t and "pod" in names and "pod" not in ax_t:
            ax_t = ("pod",) + ax_t
        ax_t = tuple(a for a in ax_t if a in names)
        size = 1
        for a in ax_t:
            size *= mesh.shape[a]
        while ax_t and dim % size != 0:
            ax_t = ax_t[1:]
            size = 1
            for a in ax_t:
                size *= mesh.shape[a]
        spec.append(ax_t if ax_t else None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


@contextlib.contextmanager
def analysis_mode(flash_q: int = 4096, flash_kv: int = 4096,
                  loss_chunk_: int = 4096):
    """Unroll every scan; coarsen chunk granularity (FLOP-invariant) so the
    unrolled HLO stays small."""
    t1 = _UNROLL.set(True)
    t2 = _FLASH_Q.set(flash_q)
    t3 = _FLASH_KV.set(flash_kv)
    t4 = _LOSS_CHUNK.set(loss_chunk_)
    try:
        yield
    finally:
        _UNROLL.reset(t1)
        _FLASH_Q.reset(t2)
        _FLASH_KV.reset(t3)
        _LOSS_CHUNK.reset(t4)
