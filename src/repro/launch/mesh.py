"""Production mesh construction (a FUNCTION — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax

from repro.dist import sharding as SH


def production_topology(*, multi_pod: bool = False):
    """(shape, axis_names) of the production mesh — the single source of
    truth for both the device mesh and its abstract twin."""
    if multi_pod:
        return (2, 16, 16), ("pod", "data", "model")
    return (16, 16), ("data", "model")


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod: (data=16, model=16).  Multi-pod: 2 pods = 512 chips with
    a leading "pod" axis (data-parallel across the cross-pod DCN/ICI)."""
    shape, axes = production_topology(multi_pod=multi_pod)
    return SH.make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally (tests / examples): 1D 'data' mesh."""
    n = len(jax.devices())
    return SH.make_mesh((n,), ("data",))


def make_abstract_production_mesh(*, multi_pod: bool = False):
    """Device-free AbstractMesh with the production topology — for spec
    construction (repro.dist.sharding) without touching jax device state."""
    shape, axes = production_topology(multi_pod=multi_pod)
    return SH.abstract_mesh(shape, axes)


# Per-chip peaks for the roofline terms, keyed by ``device_kind`` as JAX
# reports it.  Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s
# bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect over 4 ICI
# links (50 GB/s per link).  A device that is not listed has no peaks.
PEAKS = {
    "TPU v5 lite": dict(flops_bf16=197e12, hbm_bw=819e9,
                        ici_bw_per_link=50e9),
}
# The chip the production meshes above describe (a v5e pod).
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str = PRODUCTION_DEVICE_KIND) -> dict:
    """Roofline peaks of one chip of ``device_kind``; raises for a device
    without a table entry rather than assuming another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no roofline peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
