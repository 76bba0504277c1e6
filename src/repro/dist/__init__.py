"""Distribution subsystem: sharding rules.

``repro.dist.sharding`` owns every PartitionSpec the launch entry points
use (params / optimizer state / batches / decode caches) plus the CEP
engine's pattern-parallel specs (``pm_specs`` / ``run_engine_sharded``),
and builds meshes with the Auto axis types those specs need.
"""
from repro.dist import sharding  # noqa: F401
