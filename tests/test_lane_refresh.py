"""The tenant lanes' model refresh as one program over the lanes
(``refresh.refresh_lanes`` under ``MultiTenantRuntime``; DESIGN.md §7),
held bit for bit against its plain reference, ``refresh_model`` run on
each lane alone: tables, chains, ``f``, decayed observations and every
per-lane counter, with lanes that refit, skip at the observation gate,
hold NaN observations, refit a non-finite ``f``, fit non-finite tables or
pass the drift test.
On one device, and on a mesh of four virtual devices (a child process:
the device count is fixed when JAX starts), where every model and carry
leaf keeps the lanes' ``NamedSharding`` through the refresh."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime as RT
from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.data import streams
from repro.dist import sharding as SH
from repro.runtime import lanes as LN
from test_refresh_fit import _dispatches

COST = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4, c_shed_pm=5e-7,
            c_ebl=6e-5)
# The lanes4_q1 fixture's shapes (benchmarks/chip/tests/fixtures): Q1 over
# 4 symbols, window 120, 64 slots, chunk 64, bins of 16; eight lanes, two
# a device on a mesh of four.
L, CHUNK, BIN = 8, 64, 16
P_CLASS = (0.15, 0.25)
OVERLOAD = (1.4, 1.2, 1.3, 0.8)
SPARSE, POISONED, BAD_F = 1, 2, 5     # the lanes given a special case


def _deployment(weight: float = 1.0):
    specs = [dataclasses.replace(pat.make_q1(window_size=120, num_symbols=4),
                                 weight=weight)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=64, block_events=8,
                                latency_bound=0.05, gather_stats=True,
                                shedder=eng.SHED_PSPICE, **COST)
    model = eng.make_model(cp, cfg)
    rate = 1.0 / (cfg.c_base + cfg.c_match * 4)
    events = []
    for lane in range(L):
        raw = streams.gen_stock(4 * CHUNK, num_symbols=40, pattern_symbols=4,
                                hot_fraction=0.9,
                                p_class=P_CLASS[lane % len(P_CLASS)],
                                seed=300 + lane)
        events.append(streams.classify(
            specs, raw, rate=rate * OVERLOAD[lane % len(OVERLOAD)],
            seed=lane))
    return specs, cfg, model, RT.stack(events)


def _runtime(specs, cfg, model, rcfg, mesh=None, carry=None):
    return RT.MultiTenantRuntime(
        cfg, RT.broadcast_model(model, L) if carry is None else model,
        num_lanes=L, specs=specs, carry=carry, mesh=mesh,
        rt=RT.RuntimeConfig(chunk_size=CHUNK, refresh=rcfg))


def _special(carry):
    """Lane SPARSE: too few observations for the gate.  Lane POISONED: a
    NaN observation.  Lane BAD_F: an Inf in the latency ring, so ``f``
    refits non-finite."""
    host = jax.device_get(carry)
    oc, orw = host.obs_counts.copy(), host.obs_rewards.copy()
    oc[SPARSE] *= 1e-3
    oc[POISONED, 0, 1, 2] = np.nan
    lat = host.lat_samples_l.copy()
    lat[BAD_F, 3] = np.inf
    return carry._replace(obs_counts=jnp.asarray(oc),
                          obs_rewards=jnp.asarray(orw),
                          lat_samples_l=jnp.asarray(lat))


def _assert_lane_equal(got, want, what):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=what)


def _assert_lane_sharded(tree, mesh, what):
    want = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        "data"))
    for x in jax.tree.leaves(tree):
        assert isinstance(x.sharding, jax.sharding.NamedSharding), what
        assert x.sharding.is_equivalent_to(want, x.ndim), (what,
                                                           x.sharding)
        assert len(x.sharding.device_set) == mesh.size, what


def check(n_devices: int | None) -> None:
    """Two due refreshes of the lane runtime against ``refresh_model`` on
    each lane; with ``n_devices``, on a ``data`` mesh of that many."""
    specs, cfg, model, evL = _deployment()
    mesh = None if n_devices is None else SH.make_mesh(
        (n_devices,), ("data",), devices=jax.devices()[:n_devices])
    rcfg = RT.RefreshConfig(every_chunks=4, min_observations=16.0,
                            bin_size=BIN, decay=0.5, drift_threshold=1e-9)
    # The lanes' own observations: four chunks of each lane's stream.
    warm = _runtime(specs, cfg, model,
                    RT.RefreshConfig(every_chunks=0, bin_size=BIN))
    warm.push(evL)
    carry = _special(warm.carry)
    mt = _runtime(specs, cfg, warm.model, rcfg, mesh, carry)
    if mesh is not None:
        _assert_lane_sharded((mt.model, mt.carry), mesh, "placed")
    ref_states = [RT.RefreshState() for _ in range(L)]
    m_ref = [RT.unstack_lane(mt.model, lane) for lane in range(L)]
    c_ref = [RT.unstack_lane(carry, lane) for lane in range(L)]
    for round_ in range(2):
        obs = mt.carry.obs_counts
        did = mt._maybe_refresh()
        # Not donated: the observations before the refresh stay readable.
        assert not obs.is_deleted() and np.asarray(obs).shape[0] == L
        done = []
        for lane in range(L):
            m_ref[lane], c_ref[lane], d = RT.refresh_model(
                specs, cfg, m_ref[lane], c_ref[lane], rcfg,
                ref_states[lane], RT.TelemetryLog())
            done.append(d)
            what = f"round {round_} lane {lane}"
            _assert_lane_equal(RT.unstack_lane(mt.model, lane),
                               m_ref[lane], what + " model")
            _assert_lane_equal(RT.unstack_lane(mt.carry, lane),
                               c_ref[lane], what + " carry")
            got, want = mt.refresh_state[lane], ref_states[lane]
            assert (got.refresh_count, got.skipped_obs,
                    got.skipped_nonfinite, got.skipped_drift) == (
                want.refresh_count, want.skipped_obs,
                want.skipped_nonfinite, want.skipped_drift), what
            if want.last_T is None:
                assert got.last_T is None, what
            else:
                np.testing.assert_array_equal(got.last_T, want.last_T,
                                              err_msg=what)
        assert did == any(done)
        if mesh is not None:
            _assert_lane_sharded((mt.model, mt.carry), mesh,
                                 f"round {round_}")
    # Each special case did what it is there for.
    st = mt.refresh_state
    assert st[0].refresh_count == 1 and st[0].skipped_drift == 1
    assert st[SPARSE].skipped_obs == 2 and st[SPARSE].refresh_count == 0
    assert st[POISONED].skipped_nonfinite == 2
    assert st[POISONED].refresh_count == 0
    assert st[BAD_F].refresh_count == 1 and st[BAD_F].skipped_nonfinite == 1
    # The chunk step runs on what the refresh deployed, still sharded.
    mt.push(jax.tree.map(lambda x: x[:, :CHUNK], evL))
    if mesh is not None:
        _assert_lane_sharded((mt.model, mt.carry), mesh, "after a chunk")


def test_lane_refresh_equals_refresh_model_per_lane():
    check(None)


def test_nonfinite_tables_keep_every_lane():
    """Finite observations whose tables overflow in every lane (a weight
    of 1e33 over the remaining-time floor of 1e-6): no lane deploys, each
    counts the skip, as ``refresh_model`` does lane by lane."""
    specs, cfg, model, evL = _deployment(weight=1e33)
    rcfg = RT.RefreshConfig(every_chunks=4, min_observations=1.0,
                            bin_size=BIN)
    mt = _runtime(specs, cfg, model, rcfg)
    mt.push(evL)
    m_before, c_before = mt.model, mt.carry
    for lane in range(L):
        state = RT.RefreshState()
        m, c, did = RT.refresh_model(
            specs, cfg, RT.unstack_lane(m_before, lane),
            RT.unstack_lane(c_before, lane), rcfg, state, RT.TelemetryLog())
        assert not did and state.skipped_nonfinite == 1
        got = mt.refresh_state[lane]
        assert (got.refresh_count, got.skipped_nonfinite) == (0, 1)
    assert not mt._maybe_refresh()
    assert mt.model is m_before and mt.carry is c_before
    assert all(st.skipped_nonfinite == 2 for st in mt.refresh_state)


def check_patterns_sharded() -> None:
    """On a (data, model) mesh of four the refresh deploys into the
    placement ``lane_specs`` gives: lanes over ``data``, each lane's two
    queries over ``model``."""
    specs = [pat.make_q1(window_size=120, num_symbols=4),
             pat.make_q1(window_size=60, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=64, block_events=8,
                                latency_bound=0.05, gather_stats=True,
                                shedder=eng.SHED_PSPICE, **COST)
    mesh = SH.make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    n = 4
    events = []
    for lane in range(n):
        raw = streams.gen_stock(4 * CHUNK, num_symbols=40, pattern_symbols=4,
                                hot_fraction=0.9, p_class=0.25,
                                seed=400 + lane)
        events.append(streams.classify(specs, raw, rate=2000.0, seed=lane))
    mt = RT.MultiTenantRuntime(
        cfg, RT.broadcast_model(eng.make_model(cp, cfg), n), num_lanes=n,
        specs=specs, mesh=mesh,
        rt=RT.RuntimeConfig(chunk_size=CHUNK, refresh=RT.RefreshConfig(
            every_chunks=4, min_observations=1.0, bin_size=BIN)))
    sp = SH.lane_specs(mesh, cfg, n)
    assert (sp["lane_axis"], sp["pattern_axis"]) == ("data", "model")
    mt.push(RT.stack(events))
    assert all(st.refresh_count == 1 for st in mt.refresh_state)
    for got, want in zip(jax.tree.leaves(mt.model),
                         jax.tree.leaves(SH.named_tree(mesh, sp["model"]))):
        assert got.sharding.is_equivalent_to(want, got.ndim), (
            got.sharding, want)


def test_lane_refresh_on_four_devices_keeps_the_lane_sharding():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                 if p])
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "four devices: ok" in proc.stdout
    assert "lanes x patterns: ok" in proc.stdout


def test_lane_refresh_is_two_programs_and_no_restack(monkeypatch):
    """After warm-up a due refresh of every lane dispatches two compiled
    programs, the fit and the deploy, and runs no op eagerly; it never
    splits the lanes apart or stacks them again."""
    specs, cfg, model, evL = _deployment()
    rcfg = RT.RefreshConfig(every_chunks=4, min_observations=1.0,
                            bin_size=BIN)
    mt = _runtime(specs, cfg, model, rcfg)
    mt.push(evL)                               # warm: compiles both

    def refuse(*a, **k):
        raise AssertionError("the lane refresh restacked lanes")
    monkeypatch.setattr(LN, "stack", refuse)
    monkeypatch.setattr(LN, "unstack_lane", refuse)
    with _dispatches(monkeypatch) as calls:
        assert mt._maybe_refresh()             # warm again, caches cleared
        calls.clear()
        assert mt._maybe_refresh()
        assert len(calls) == 2, calls
    assert all(st.refresh_count == 3 for st in mt.refresh_state)


@pytest.mark.parametrize("lanes_ready", [0, 3, L])
def test_lane_counters(lanes_ready):
    """A due refresh counts every lane once: ``lanes_refit`` the lanes
    that deployed, ``lanes_skipped`` the rest."""
    specs, cfg, model, evL = _deployment()
    rcfg = RT.RefreshConfig(every_chunks=4, min_observations=16.0,
                            bin_size=BIN)
    warm = _runtime(specs, cfg, model,
                    RT.RefreshConfig(every_chunks=0, bin_size=BIN))
    warm.push(evL)
    oc = np.asarray(warm.carry.obs_counts).copy()
    oc[lanes_ready:] = 0.0
    mt = _runtime(specs, cfg, warm.model, rcfg,
                  carry=warm.carry._replace(obs_counts=jnp.asarray(oc)))
    mt.telemetry.begin_chunk()
    assert mt._maybe_refresh() == (lanes_ready > 0)
    held = mt.telemetry._chunk_held["counts"]
    assert held == {"lanes_refit": lanes_ready,
                    "lanes_skipped": L - lanes_ready}


if __name__ == "__main__":
    check(len(jax.devices()))
    print("four devices: ok")
    check_patterns_sharded()
    print("lanes x patterns: ok")
