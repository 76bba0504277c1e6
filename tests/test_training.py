"""Training-substrate tests: optimizer, checkpointing, compression,
serving scheduler, and the multi-device dry-run plumbing (subprocess)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.scheduler import (SchedulerConfig, run_simulation,
                                     synth_workload)
from repro.training import checkpoint as CK
from repro.training import compression as COMP
from repro.training import optimizer as O


class TestOptimizer:
    def test_adamw_decreases_quadratic(self):
        cfg = O.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1)
        params = {"w": jnp.array([5.0, -3.0])}
        opt = O.init_opt_state(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, opt = O.adamw_update(cfg, params, grads, opt)
        assert float(jnp.abs(params["w"]).max()) < 0.1

    def test_clip_by_global_norm(self):
        g = {"a": jnp.ones(100) * 10.0}
        clipped, norm = O.clip_by_global_norm(g, 1.0)
        assert abs(float(O.global_norm(clipped)) - 1.0) < 1e-4
        assert abs(float(norm) - 100.0) < 1e-3

    def test_nested_structure_preserved(self):
        cfg = O.AdamWConfig()
        params = {"l": {"w": jnp.ones((2, 2)), "b": jnp.zeros(2)}}
        opt = O.init_opt_state(params)
        grads = jax.tree.map(jnp.ones_like, params)
        p2, o2 = O.adamw_update(cfg, params, grads, opt)
        assert set(p2) == {"l"} and set(p2["l"]) == {"w", "b"}
        assert int(o2["step"]) == 1


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
                "b": {"c": np.ones(5, np.int32)}}
        CK.save(str(tmp_path), 7, tree)
        out = CK.restore(str(tmp_path), tree)
        np.testing.assert_array_equal(out["a"], tree["a"])
        np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])

    def test_latest_and_gc(self, tmp_path):
        tree = {"x": np.zeros(3)}
        for s in (1, 2, 3, 4, 5):
            CK.save(str(tmp_path), s, tree, keep_last=2)
        assert CK.latest_step(str(tmp_path)) == 5
        assert sorted(os.listdir(tmp_path)) == ["step_00000004",
                                                "step_00000005"]

    def test_atomicity_no_partial_dirs(self, tmp_path):
        tree = {"x": np.zeros(3)}
        CK.save(str(tmp_path), 1, tree)
        assert not any(d.startswith(".tmp") for d in os.listdir(tmp_path))

    def test_shape_mismatch_raises(self, tmp_path):
        CK.save(str(tmp_path), 1, {"x": np.zeros(3)})
        with pytest.raises(ValueError):
            CK.restore(str(tmp_path), {"x": np.zeros(4)})


class TestCompression:
    def test_quantize_roundtrip_bounded_error(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3
        q, s = COMP.quantize_int8(x)
        err = jnp.abs(COMP.dequantize_int8(q, s) - x).max()
        assert float(err) <= float(s) * 0.5 + 1e-6

    def test_error_feedback_unbiased_over_time(self):
        g = jax.random.normal(jax.random.PRNGKey(1), (512,))
        err = jnp.zeros_like(g)
        acc = jnp.zeros_like(g)
        for _ in range(100):
            deq, err = COMP.compress_decompress(g, err)
            acc += deq
        rel = float(jnp.abs(acc - 100 * g).max() / jnp.abs(100 * g).max())
        assert rel < 1e-3

    def test_compressed_psum_in_shard_map(self):
        """int8 EF all-reduce across the host devices (≥1)."""
        mesh = jax.make_mesh((len(jax.devices()),), ("d",))
        from jax.sharding import PartitionSpec as P

        def f(g, e):
            m, ne = COMP.compressed_psum(g[0], e[0], "d")
            return m[None], ne[None]

        n = len(jax.devices())
        g = jnp.stack([jnp.full((64,), float(i + 1)) for i in range(n)])
        e = jnp.zeros_like(g)
        mfn = jax.shard_map(f, mesh=mesh, in_specs=(P("d"), P("d")),
                            out_specs=(P("d"), P("d")))
        mean, _ = mfn(g, e)
        expect = np.mean([i + 1 for i in range(n)])
        np.testing.assert_allclose(np.asarray(mean[0]), expect, rtol=1e-2)


class TestServingScheduler:
    def test_pspice_beats_baselines(self):
        res = {}
        for pol in ("pspice", "random", "admission"):
            cfg = SchedulerConfig(policy=pol, max_slots=32, slo=1.5, seed=1)
            reqs = synth_workload(400, rate=90.0, cfg=cfg, seed=5)
            res[pol] = run_simulation(cfg, reqs)["goodput"]
        assert res["pspice"] >= res["random"] - 0.02
        assert res["pspice"] > res["admission"]

    def test_all_requests_accounted(self):
        cfg = SchedulerConfig(policy="pspice", max_slots=16, slo=1.0)
        reqs = synth_workload(100, rate=50.0, cfg=cfg, seed=2)
        m = run_simulation(cfg, reqs)
        assert m["completed"] + m["evicted"] == 100


@pytest.mark.slow
class TestDryRunSubprocess:
    """The 512-device dry-run runs in a subprocess (device count is locked
    at first jax init, so the main test process must stay at 1 device)."""

    def test_single_cell_lowers_and_compiles(self):
        env = dict(os.environ, PYTHONPATH="src")
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch",
             "internlm2-1.8b", "--shape", "decode_32k"],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=1800)
        assert out.returncode == 0, out.stderr[-2000:]
        assert '"status": "ok"' in out.stdout
