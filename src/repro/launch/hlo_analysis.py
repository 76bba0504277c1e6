"""Roofline-term extraction from a compiled (SPMD-partitioned) module.

compute term    = HLO_FLOPs / (chips × 197 TFLOP/s bf16)
memory term     = HLO_bytes / (chips × 819 GB/s)
collective term = collective_bytes / (chips × 50 GB/s/link)

cost_analysis() supplies FLOPs/bytes.  collective_bytes is parsed from
``compiled.as_text()`` post-partitioning HLO: we sum the OPERAND sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (operand types are inlined in HLO long text).
"""
from __future__ import annotations

import dataclasses
import re

from repro.launch import mesh as M

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*((?:\(?\s*[a-z]+\d*[a-z0-9]*\[[\d,]*\]"
    r"[^)=]*\)?)?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_shape_bytes(fragment: str) -> int:
    """Total bytes of every typed shape in an HLO text fragment.

    Shared with ``repro.analysis.rules`` (gather/scatter result budgets):
    pass the result-type portion of an op line (everything left of the
    op name) and get the summed byte size — tuple results sum their
    elements, unknown dtypes are skipped."""
    return sum(_shape_bytes(dt, dims)
               for dt, dims in _SHAPE_RE.findall(fragment)
               if dt in _DTYPE_BYTES)


@dataclasses.dataclass
class CollectiveStats:
    """Per-device WIRE bytes (ring-algorithm volumes) by collective kind."""
    bytes_by_kind: dict
    count_by_kind: dict

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    def scaled(self, factor: float) -> "CollectiveStats":
        return CollectiveStats(
            {k: v * factor for k, v in self.bytes_by_kind.items()},
            dict(self.count_by_kind))

    def minus(self, other: "CollectiveStats") -> "CollectiveStats":
        return CollectiveStats(
            {k: max(0.0, self.bytes_by_kind[k] - other.bytes_by_kind[k])
             for k in self.bytes_by_kind},
            {k: max(0, self.count_by_kind[k] - other.count_by_kind[k])
             for k in self.count_by_kind})

    def plus(self, other: "CollectiveStats") -> "CollectiveStats":
        return CollectiveStats(
            {k: self.bytes_by_kind[k] + other.bytes_by_kind[k]
             for k in self.bytes_by_kind},
            {k: self.count_by_kind[k] + other.count_by_kind[k]
             for k in self.count_by_kind})


def _group_size(line: str) -> int:
    m = _GROUP_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUP_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return 2


def _wire_factor(kind: str, g: int) -> float:
    """Per-device ring wire volume as a multiple of the RESULT bytes."""
    if kind == "all-reduce":
        return 2.0 * (g - 1) / g
    if kind == "all-gather":
        return (g - 1) / g
    if kind == "reduce-scatter":
        return float(g - 1)       # operand = result × g
    if kind == "all-to-all":
        return (g - 1) / g
    return 1.0                    # collective-permute


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum per-device wire bytes of every collective in post-SPMD HLO.

    Operand names carry no inline types in modern HLO text, so sizes come
    from the RESULT type(s) with kind-specific ring factors (result ==
    operand for all-reduce/all-to-all/permute; all-gather result is the
    gathered array; reduce-scatter result is one shard)."""
    bytes_by_kind: dict = {k: 0.0 for k in _COLLECTIVES}
    count_by_kind: dict = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        result_types, kind, startdone = m.group(1), m.group(2), m.group(3)
        if startdone == "-done":
            continue  # counted at -start
        total = 0
        for dt, dims in _SHAPE_RE.findall(result_types):
            if dt in _DTYPE_BYTES:
                total += _shape_bytes(dt, dims)
        g = _group_size(line)
        bytes_by_kind[kind] += total * _wire_factor(kind, g)
        count_by_kind[kind] += 1
    return CollectiveStats(bytes_by_kind, count_by_kind)


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    collective_bytes: float
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float
    collectives: CollectiveStats
    per_device_mem: float

    def row(self) -> dict:
        return {
            "flops": self.flops, "bytes": self.bytes_accessed,
            "coll_bytes": self.collective_bytes, "chips": self.chips,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "per_device_mem_gb": self.per_device_mem / 1e9,
            "coll_by_kind": self.collectives.bytes_by_kind,
        }


def analyze(compiled, chips: int, model_flops: float = 0.0,
            hlo_text: str | None = None) -> Roofline:
    """Roofline terms from one compiled executable.

    cost_analysis() on a partitioned module reports PER-PARTITION numbers;
    we normalize everything to per-chip seconds.
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = parse_collectives(text)

    # cost_analysis flops are per-partition (the module is the per-device
    # program) — per-chip time is direct.
    pk = M.peaks()
    compute_s = flops / pk["flops_bf16"]
    memory_s = byts / pk["hbm_bw"]
    collective_s = coll.total_bytes / pk["ici_bw_per_link"]
    dominant = max(
        (("compute", compute_s), ("memory", memory_s),
         ("collective", collective_s)), key=lambda kv: kv[1])[0]
    try:
        mem = compiled.memory_analysis()
        # Peak live bytes: arguments + outputs + XLA temp buffers, MINUS
        # the bytes where an output aliases a donated input (donation
        # means those outputs occupy the argument's storage, not new
        # memory — counting both would double the engine's carry, which
        # is the dominant term for run_engine_chunk).
        per_dev = (getattr(mem, "argument_size_in_bytes", 0)
                   + getattr(mem, "output_size_in_bytes", 0)
                   - getattr(mem, "alias_size_in_bytes", 0)
                   + getattr(mem, "temp_size_in_bytes", 0))
    except Exception:
        per_dev = 0
    useful = model_flops / (flops * chips) if flops else 0.0
    return Roofline(flops=flops, bytes_accessed=byts,
                    collective_bytes=coll.total_bytes, chips=chips,
                    compute_s=compute_s, memory_s=memory_s,
                    collective_s=collective_s, dominant=dominant,
                    model_flops=model_flops, useful_ratio=useful,
                    collectives=coll, per_device_mem=per_dev)
