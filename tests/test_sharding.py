"""Sharding-rule tests (divisibility fallbacks, spec shapes) — single device,
abstract mesh only."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.dist import sharding as SH
from repro.models import transformer as T


@pytest.fixture(scope="module")
def mesh():
    # AbstractMesh with the production topology: no devices needed for
    # spec construction (SH.abstract_mesh: Auto axes, no devices).
    from repro.launch import mesh as M
    return M.make_abstract_production_mesh()


def _specs_for(arch, mesh):
    cfg = registry.get_config(arch)
    params = jax.eval_shape(lambda: T.init_params(cfg,
                                                  jax.random.key(0)))
    return cfg, params, SH.param_specs(mesh, cfg, params)


class TestParamSpecs:
    def test_dense_attention_head_sharded(self, mesh):
        cfg, params, specs = _specs_for("starcoder2-15b", mesh)
        assert specs["layers"]["attn"]["wq"] == P(None, None, "model", None)
        # kv heads = 4 < 16 → replicated
        assert specs["layers"]["attn"]["wk"] == P(None, None, None, None)
        assert specs["layers"]["mlp"]["wi"] == P(None, None, "model")

    def test_minitron_falls_back_to_replicated_attention(self, mesh):
        cfg, params, specs = _specs_for("minitron-4b", mesh)
        assert specs["layers"]["attn"]["wq"] == P(None, None, None, None)
        assert specs["layers"]["mlp"]["wi"] == P(None, None, "model")

    def test_fsdp_shards_over_data_too(self, mesh):
        cfg, params, specs = _specs_for("qwen1.5-110b", mesh)
        assert specs["layers"]["mlp"]["wi"] == P(None, "data", "model")
        assert specs["embed"] == P("model", "data")

    def test_moe_experts_on_model_axis(self, mesh):
        cfg, params, specs = _specs_for("deepseek-moe-16b", mesh)
        assert specs["layers"]["moe"]["wi"] == P(None, "model", None, None)
        assert specs["layers"]["moe"]["router"] == P(None, None, "model")

    def test_mamba_channels_sharded(self, mesh):
        cfg, params, specs = _specs_for("mamba2-1.3b", mesh)
        assert specs["layers"]["mamba"]["wz"] == P(None, None, "model")
        assert specs["layers"]["mamba"]["wB"] == P(None, None, None)

    def test_every_leaf_divisible(self, mesh):
        """Property: every sharded dim divides evenly over its axes."""
        for arch in registry.ARCH_IDS:
            cfg, params, specs = _specs_for(arch, mesh)
            flat_p = jax.tree_util.tree_leaves(params)
            flat_s = jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P))
            for leaf, spec in zip(flat_p, flat_s):
                for dim, ax in zip(leaf.shape, tuple(spec)):
                    if ax is None:
                        continue
                    axes = (ax,) if isinstance(ax, str) else ax
                    size = int(np.prod([mesh.shape[a] for a in axes]))
                    assert dim % size == 0, (arch, leaf.shape, spec)


class TestBatchAndCacheSpecs:
    def test_batch_axes_fallback(self, mesh):
        assert SH.batch_axes(mesh, 256) == ("data",)
        assert SH.batch_axes(mesh, 1) is None

    def test_multipod_batch_axes(self):
        mp = SH.abstract_mesh((2, 16, 16), ("pod", "data", "model"))
        assert SH.batch_axes(mp, 256) == ("pod", "data")
        assert SH.batch_axes(mp, 16) == ("data",)

    def test_cache_sequence_sharded_over_model(self, mesh):
        from repro.models import decode as D
        cfg = registry.get_config("starcoder2-15b")
        cache = jax.eval_shape(lambda: D.init_cache(cfg, 128, 32768))
        specs = SH.cache_specs(mesh, cfg, cache)
        assert specs["k"] == P(None, "data", "model", None, None)
        assert specs["pos"] == P()
