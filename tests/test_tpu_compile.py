"""Every engine kernel compiles for a TPU v5e (no chip needed).

The TPU compiler ships with jax; it compiles for a described topology
that is not attached.  Interpret-mode tests cannot show what Mosaic
refuses (unaligned layouts, unsupported primitives, VMEM overflow), so
each kernel variant the served path, the runner and the ``pallas``
backend use is compiled here with ``interpret=False`` at the stock
scenario's size (P=3 patterns, N=2048 PM slots, W=32 events per block)
and must contain a Mosaic custom call.  Nothing runs: this proves the
kernels compile, not what they compute (tests/test_block_backend.py and
tests/test_oracle.py pin that, interpreted; ``chip_smoke.py`` on a chip).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this module.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.data import streams
from repro.dist import sharding as SH
from repro.kernels import block_step as kblock
from repro.kernels import ops as kops

N_PMS, W = 2048, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """A (data=4) mesh over the described host, replicated inputs."""
    mesh = SH.make_mesh((4,), ("data",), devices=topo.devices)
    return mesh, NamedSharding(mesh, PartitionSpec())


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def _assert_mosaic(fn, args, sharding):
    compiled = jax.jit(fn).lower(*_shapes(args, sharding)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _stock_specs():
    return streams.get_scenario("stock").make_specs()


def _mixed_specs():
    return [pat.make_q1(window_size=600, num_symbols=10),
            pat.make_q3(any_n=3, window_size=150),
            pat.make_q4(any_n=3, window_size=600, slide=200)]


def _events(n, P):
    return eng.EventBatch(
        ev_class=jnp.zeros((n, P), jnp.int32),
        ev_bind=jnp.zeros((n, P), jnp.int32),
        ev_open=jnp.zeros((n, P), bool), ev_id=jnp.zeros((n,), jnp.int32),
        ev_rand=jnp.zeros((n,), jnp.float32),
        ebl_raw=jnp.zeros((n,), jnp.float32),
        arrival=jnp.zeros((n,), jnp.float32))


def _setup(specs, **kw):
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=N_PMS, block_events=W,
                                backend=eng.BACKEND_PALLAS_BLOCK, **kw)
    # A utility table with the stock run's bin count (2400 / 64 events).
    model = eng.make_model(cp, cfg,
                           ut_tables=jnp.ones((len(specs), 38,
                                               cp.max_states), jnp.float32),
                           ut_bins=jnp.full((len(specs),), 64, jnp.int32))
    return cfg, model, eng.init_carry(cfg), _events(W, cfg.num_patterns)


BLOCK_VARIANTS = {
    "none": dict(shedder=eng.SHED_NONE),
    "ebl": dict(shedder=eng.SHED_EBL),
    "pspice_fused_emit": dict(shedder=eng.SHED_PSPICE, emit_matches=True),
    "pmbl_fused": dict(shedder=eng.SHED_PMBL),
    "gather_stats": dict(shedder=eng.SHED_NONE, gather_stats=True),
    "replay": dict(shedder=eng.SHED_PSPICE, block_shed="replay"),
}


@pytest.mark.parametrize("variant", sorted(BLOCK_VARIANTS))
def test_block_step_compiles(one_chip, variant):
    cfg, model, carry, blk = _setup(_stock_specs(), **BLOCK_VARIANTS[variant])

    def step(model, carry, blk):
        return kblock.block_step(cfg, model, carry, blk, 0, 0, W,
                                 interpret=False)

    _assert_mosaic(step, (model, carry, blk), one_chip)


def test_block_step_any_patterns_compile(one_chip):
    """ANY distinctness sets and slide-window spawns (mixed census)."""
    cfg, model, carry, blk = _setup(_mixed_specs(), shedder=eng.SHED_PSPICE,
                                    emit_matches=True, gather_stats=True)
    assert (cfg.kinds, cfg.spawn_modes) == ("mixed", "mixed")

    def step(model, carry, blk):
        return kblock.block_step(cfg, model, carry, blk, 0, 0, W,
                                 interpret=False)

    _assert_mosaic(step, (model, carry, blk), one_chip)


def test_block_step_lanes_compile(one_chip):
    """The tenant-lane form: the kernel vmapped over 8 lanes."""
    L = 8
    cfg, model, carry, blk = _setup([pat.make_q1(window_size=400,
                                                 num_symbols=4)],
                                    shedder=eng.SHED_PSPICE)
    stack = lambda t: jax.tree.map(                          # noqa: E731
        lambda x: jnp.broadcast_to(x, (L,) + jnp.shape(x)), t)

    def step(model, carry, blk):
        return jax.vmap(lambda m, c, b: kblock.block_step(
            cfg, m, c, b, 0, 0, W, interpret=False))(model, carry, blk)

    _assert_mosaic(step, (stack(model), stack(carry), stack(blk)), one_chip)


def test_engine_chunk_compiles(one_chip, monkeypatch):
    """The served path's chunk step (block scan around the kernel)."""
    monkeypatch.setattr(kops, "default_interpret", lambda: False)
    cfg, model, carry, _ = _setup(_stock_specs(), shedder=eng.SHED_PSPICE,
                                  emit_matches=True)
    ev = _events(4 * W, cfg.num_patterns)

    def chunk(model, ev, carry, start):
        return eng._scan_events_backend(cfg, model, ev, carry, start)

    _assert_mosaic(chunk, (model, ev, carry, jnp.int32(0)), one_chip)


def test_nfa_advance_compiles(one_chip):
    def adv(state, bind, active, tcol):
        return kops.nfa_advance_pallas(state, bind, active, tcol, 3, 4, 1,
                                       interpret=False)

    args = (jnp.zeros((N_PMS,), jnp.int32), jnp.zeros((N_PMS,), jnp.int32),
            jnp.zeros((N_PMS,), bool), jnp.zeros((8,), jnp.int32))
    _assert_mosaic(adv, args, one_chip)


def test_utility_lookup_dyn_compiles(one_chip):
    def look(state, r_w, active, table, bs):
        return kops.utility_lookup_dyn_pallas(state, r_w, active, table, bs,
                                              interpret=False)

    args = (jnp.zeros((N_PMS,), jnp.int32), jnp.zeros((N_PMS,), jnp.int32),
            jnp.zeros((N_PMS,), bool), jnp.zeros((38, 8), jnp.float32),
            jnp.float32(64.0))
    _assert_mosaic(look, args, one_chip)


def test_utility_histogram_compiles(one_chip):
    def hist(u, lo, hi):
        return kops.utility_histogram_pallas(u, lo, hi, nbins=128,
                                             interpret=False)

    args = (jnp.zeros((3 * N_PMS,), jnp.float32), jnp.float32(0.0),
            jnp.float32(1.0))
    _assert_mosaic(hist, args, one_chip)


def test_sharded_engine_compiles_for_four_chips(four_chips, monkeypatch):
    """The soccer scenario's 8 Q3 patterns, 2 per chip, pattern-parallel."""
    monkeypatch.setattr(kops, "default_interpret", lambda: False)
    mesh, replicated = four_chips
    cfg, model, carry, _ = _setup(streams.get_scenario("soccer").specs(),
                                  emit_matches=True)

    def run(model, ev, carry):
        return SH.run_engine_sharded(cfg, model, ev, carry, mesh=mesh)

    _assert_mosaic(run, (model, _events(4 * W, cfg.num_patterns), carry),
                   replicated)


def test_sharded_lanes_compile_for_four_chips(four_chips, monkeypatch):
    """8 tenant lanes over the 4-chip mesh (the runtime's mesh step)."""
    monkeypatch.setattr(kops, "default_interpret", lambda: False)
    mesh, replicated = four_chips
    L = 8
    cfg, model, carry, _ = _setup([pat.make_q1(window_size=400,
                                               num_symbols=4)],
                                  shedder=eng.SHED_PSPICE, gather_stats=True)
    stack = lambda t: jax.tree.map(                          # noqa: E731
        lambda x: jnp.broadcast_to(x, (L,) + jnp.shape(x)), t)
    fn = SH._lanes_sharded_fn(cfg, mesh, L, "data", "model")
    assert fn is not None, "8 lanes must shard over 4 devices"
    args = (stack(model), stack(_events(4 * W, 1)), stack(carry),
            jnp.zeros((1,), jnp.int32))
    compiled = fn.lower(*_shapes(args, replicated)).compile()
    assert "tpu_custom_call" in compiled.as_text()
