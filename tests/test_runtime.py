"""repro.runtime: chunked-execution equivalence, tenant lanes, online
refresh, drifting streams, and the PR's satellite fixes (DESIGN.md §7)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.data import streams
from repro import runtime as RT
from repro.dist import sharding as SH

COST = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4, c_shed_pm=1.5e-6,
            c_ebl=6e-5)
N_EVENTS = 2000


def _assert_tree_equal(a, b, what=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=what)


@pytest.fixture(scope="module")
def setup():
    """One shedding config shared by every test (compile-cache friendly):
    tight latency bound + overload rate, so the shed cond actually fires."""
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=48, latency_bound=0.005,
                                gather_stats=True, shedder=eng.SHED_PSPICE,
                                **COST)
    model = eng.make_model(cp, cfg)
    rate = 3.0 / (cfg.c_base + cfg.c_match * 0.3 * cfg.max_pms)

    def make_events(seed, rate_mult=1.0, n=N_EVENTS):
        raw = streams.gen_stock(n, num_symbols=50, pattern_symbols=4,
                                p_class=0.05, seed=100 + seed)
        return streams.classify(specs, raw, rate=rate * rate_mult, seed=seed)

    return specs, cfg, model, make_events


class TestChunkedEquivalence:
    @pytest.mark.parametrize("chunk", [64, 100, 256, N_EVENTS])
    def test_chunked_bitwise_equals_monolithic(self, setup, chunk):
        """Several chunk sizes — including non-divisors of the stream
        length — replay the monolithic scan bit for bit."""
        _, cfg, model, make_events = setup
        ev = make_events(0)
        c_mono, o_mono = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        assert float(c_mono.pms_shed) > 0, "fixture must actually shed"

        carry = eng.init_carry(cfg)
        outs = []
        for start, piece in RT.iter_chunks(ev, chunk):
            carry, o = eng.run_engine_chunk(cfg, model, piece, carry,
                                            jnp.int32(start))
            outs.append(o)
        o_cat = jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)
        _assert_tree_equal(c_mono, carry, f"carry, chunk={chunk}")
        _assert_tree_equal(o_mono, o_cat, f"outs, chunk={chunk}")

    def test_stream_runtime_ragged_pushes(self, setup):
        """Pushes of arbitrary sizes re-chunk through the buffer; flush
        drains the tail; the result is still bitwise-identical."""
        _, cfg, model, make_events = setup
        ev = make_events(0)
        c_mono, _ = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        srt = RT.StreamRuntime(cfg, model,
                               rt=RT.RuntimeConfig(chunk_size=256))
        for s in range(0, N_EVENTS, 700):
            srt.push(RT.slice_events(ev, s, min(s + 700, N_EVENTS)))
        srt.flush()
        _assert_tree_equal(c_mono, srt.carry, "ragged pushes")
        assert srt.events_processed == N_EVENTS


class TestTenantLanes:
    L = 3

    @pytest.fixture(scope="class")
    def lane_run(self, setup):
        _, cfg, model, make_events = setup
        evs = [make_events(i, rate_mult=1.0 + 0.2 * i)
               for i in range(self.L)]
        evL = RT.stack(evs)
        mL = RT.broadcast_model(model, self.L)
        cL, oL = RT.run_chunk_lanes(cfg, mL, evL,
                                    RT.init_lane_carries(cfg, self.L),
                                    jnp.int32(0))
        return evs, evL, mL, cL, oL

    def test_lanes_bitwise_equal_sequential(self, setup, lane_run):
        _, cfg, model, _ = setup
        evs, _, _, cL, oL = lane_run
        assert float(np.asarray(cL.pms_shed).sum()) > 0
        for lane in range(self.L):
            c_i, o_i = eng.run_engine(cfg, model, evs[lane],
                                      eng.init_carry(cfg, seed=lane))
            _assert_tree_equal(c_i, RT.unstack_lane(cL, lane),
                               f"lane {lane} carry")
            _assert_tree_equal(o_i, RT.unstack_lane(oL, lane),
                               f"lane {lane} outs")

    def test_merge_carries(self, setup, lane_run):
        _, cfg, _, _ = setup
        _, _, _, cL, _ = lane_run
        merged = eng.merge_carries(cL)
        P = cfg.num_patterns
        assert merged.complex_count.shape == (self.L * P,)
        assert merged.pms.active.shape == (self.L * P, cfg.max_pms)
        np.testing.assert_allclose(
            np.asarray(merged.complex_count),
            np.asarray(cL.complex_count).reshape(-1))
        assert float(merged.pms_shed) == pytest.approx(
            float(np.asarray(cL.pms_shed).sum()))
        assert float(merged.sim_time) == pytest.approx(
            float(np.asarray(cL.sim_time).max()))

    def test_sharded_lanes_match_vmapped(self, setup, lane_run):
        """On the host's (1-device) mesh the shard_map path must agree
        exactly with the plain lane-batched path."""
        _, cfg, _, _ = setup
        _, evL, mL, cL, oL = lane_run
        c_sh, o_sh = SH.run_chunk_lanes_sharded(
            cfg, mL, evL, RT.init_lane_carries(cfg, self.L), jnp.int32(0))
        _assert_tree_equal(cL, c_sh, "sharded carry")
        _assert_tree_equal(oL, o_sh, "sharded outs")

    def test_multitenant_runtime_matches_lane_scan(self, setup, lane_run):
        _, cfg, _, _ = setup
        _, evL, mL, cL, _ = lane_run
        mt = RT.MultiTenantRuntime(cfg, mL, num_lanes=self.L,
                                   rt=RT.RuntimeConfig(chunk_size=512))
        mt.push(evL, flush=True)
        _assert_tree_equal(cL, mt.carry, "runtime carry")
        assert mt.events_processed == self.L * N_EVENTS

    def test_lane_refresh_equals_per_lane_refresh_model(self, setup,
                                                        lane_run):
        """A MultiTenantRuntime refresh is ``refresh_model`` on each lane
        alone, lanes restacked; a poisoned lane keeps its model."""
        specs, cfg, _, _ = setup
        _, _, mL, cL, _ = lane_run
        cL = cL._replace(obs_counts=cL.obs_counts.at[1, 0, 0, 1].set(
            jnp.nan))
        rcfg = RT.RefreshConfig(every_chunks=2, min_observations=1.0)
        mt = RT.MultiTenantRuntime(cfg, mL, num_lanes=self.L, specs=specs,
                                   carry=cL,
                                   rt=RT.RuntimeConfig(chunk_size=512,
                                                       refresh=rcfg))
        m0 = mt.model
        assert mt._maybe_refresh()
        for lane in range(self.L):
            state = RT.RefreshState()
            m, c, did = RT.refresh_model(
                specs, cfg, RT.unstack_lane(m0, lane),
                RT.unstack_lane(cL, lane), rcfg, state, RT.TelemetryLog())
            assert did == (lane != 1)
            _assert_tree_equal(m, RT.unstack_lane(mt.model, lane),
                               f"lane {lane} model")
            _assert_tree_equal(c, RT.unstack_lane(mt.carry, lane),
                               f"lane {lane} carry")
            got = mt.refresh_state[lane]
            assert (got.refresh_count, got.skipped_nonfinite,
                    got.skipped_obs) == (state.refresh_count,
                                         state.skipped_nonfinite,
                                         state.skipped_obs)
            if did:
                np.testing.assert_array_equal(got.last_T, state.last_T)


class TestLaneSpecs:
    def _cfg(self, p):
        return eng.EngineConfig(num_patterns=p, max_states=4, max_classes=4,
                                max_pms=32)

    def test_two_axis_mesh_composes_lanes_and_patterns(self):
        from jax.sharding import PartitionSpec as P
        mesh = SH.abstract_mesh((2, 2), ("data", "model"))
        sp = SH.lane_specs(mesh, self._cfg(4), num_lanes=4)
        assert sp["lane_axis"] == "data" and sp["pattern_axis"] == "model"
        assert sp["carry"].pms.active == P("data", "model", None)
        assert sp["events"].ev_class == P("data", None, "model")
        assert sp["carry"].sim_time == P("data")
        assert sp["out"].l_e == P("data", None)

    def test_indivisible_lane_count_falls_back(self):
        mesh = SH.abstract_mesh((2, 2), ("data", "model"))
        sp = SH.lane_specs(mesh, self._cfg(4), num_lanes=3)
        assert sp["lane_axis"] is None and sp["pattern_axis"] == "model"

    def test_same_axis_shards_lanes_only(self):
        mesh = SH.abstract_mesh((4,), ("data",))
        sp = SH.lane_specs(mesh, self._cfg(4), num_lanes=4,
                           pattern_axis="data")
        assert sp["lane_axis"] == "data" and sp["pattern_axis"] is None


class TestChunkBuffer:
    def _ev(self, n, tag=0):
        return eng.EventBatch(
            ev_class=jnp.full((n, 1), tag, jnp.int32),
            ev_bind=jnp.zeros((n, 1), jnp.int32),
            ev_open=jnp.zeros((n, 1), bool),
            ev_id=jnp.arange(n, dtype=jnp.int32),
            ev_rand=jnp.zeros((n,), jnp.float32),
            ebl_raw=jnp.zeros((n,), jnp.float32),
            arrival=jnp.arange(n, dtype=jnp.float32))

    def test_ragged_pushes_rechunk(self):
        buf = RT.ChunkBuffer(64)
        got = buf.push(self._ev(100))
        assert [(s, RT.num_events(e)) for s, e in got] == [(0, 64)]
        assert buf.pending == 36
        got = buf.push(self._ev(100))
        assert [(s, RT.num_events(e)) for s, e in got] == [(64, 64), (128, 64)]
        got = buf.drain()
        assert [(s, RT.num_events(e)) for s, e in got] == [(192, 8)]
        assert buf.pending == 0 and buf.drain() == []

    def test_lane_stacked_axis(self):
        buf = RT.ChunkBuffer(32, axis=1)
        evL = RT.stack([self._ev(50), self._ev(50, tag=1)])
        got = buf.push(evL)
        assert len(got) == 1
        start, piece = got[0]
        assert start == 0 and piece.ev_class.shape == (2, 32, 1)
        (start, piece), = buf.drain()
        assert start == 32 and piece.ev_class.shape == (2, 18, 1)

    def test_outputs_never_alias_pushed_batch(self):
        """Full-range jax slices alias their input; everything the buffer
        hands out feeds DONATING jits, so it must own its buffers — a
        chunk-multiple push or a drained sub-chunk tail returning the
        caller's own arrays would let donation delete them."""
        ev = self._ev(64)
        _, region, n = RT.ChunkBuffer(64).push_region(ev)
        assert n == 1
        for a, b in zip(jax.tree.leaves(region), jax.tree.leaves(ev)):
            assert a is not b
        buf = RT.ChunkBuffer(64)
        tail_in = self._ev(10)
        assert buf.push_region(tail_in)[2] == 0
        (_, tail), = buf.drain()
        for a, b in zip(jax.tree.leaves(tail), jax.tree.leaves(tail_in)):
            assert a is not b
        (_, piece), = RT.ChunkBuffer(64).push(self._ev(64))
        for a, b in zip(jax.tree.leaves(piece), jax.tree.leaves(ev)):
            assert a is not b

    def test_zero_length_push(self):
        """An empty push is a no-op at every buffer state: empty buffer,
        buffered tail, and interleaved with real pushes."""
        buf = RT.ChunkBuffer(64)
        start, region, n = buf.push_region(self._ev(0))
        assert (start, region, n) == (0, None, 0) and buf.pending == 0
        assert buf.push(self._ev(0)) == [] and buf.drain() == []
        # with a buffered tail the empty push must not disturb it
        buf.push(self._ev(50))
        assert buf.push(self._ev(0)) == [] and buf.pending == 50
        got = buf.push(self._ev(30))
        assert [(s, RT.num_events(e)) for s, e in got] == [(0, 64)]
        assert buf.pending == 16

    def test_zero_length_push_through_runtime(self, setup):
        """StreamRuntime.push of an empty batch returns no stats and does
        not perturb the stream (bitwise)."""
        _, cfg, model, make_events = setup
        ev = make_events(0)
        c_mono, _ = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        srt = RT.StreamRuntime(cfg, model,
                               rt=RT.RuntimeConfig(chunk_size=256))
        assert srt.push(RT.slice_events(ev, 0, 0)) == []
        srt.push(ev)
        assert srt.push(RT.slice_events(ev, 0, 0)) == []
        srt.flush()
        _assert_tree_equal(c_mono, srt.carry, "empty pushes interleaved")

    def test_push_larger_than_one_group(self, setup):
        """A single push spanning MANY chunk groups (here 2000 events =
        8 chunks at group_chunks=3: groups of 3/3/2 + a short tail) splits
        correctly and stays bitwise-identical to the monolithic scan."""
        _, cfg, model, make_events = setup
        ev = make_events(0)
        c_mono, _ = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        srt = RT.StreamRuntime(cfg, model, rt=RT.RuntimeConfig(
            chunk_size=250, group_chunks=3))
        stats = srt.push(ev, flush=True)
        assert len(stats) == 8   # 2000 events = 8 chunks, groups of 3/3/2
        _assert_tree_equal(c_mono, srt.carry, "one push, many groups")

    def test_ragged_pushes_interleaved_with_refresh_boundaries(self, setup):
        """Ragged pushes + grouped dispatch + refresh cadence: groups must
        truncate at refresh boundaries regardless of push phase, so the
        grouped runtime refreshes on exactly the same chunks — and ends in
        exactly the same state — as chunk-at-a-time execution."""
        specs, cfg, model, make_events = setup
        ev = make_events(0)
        rcfg = RT.RefreshConfig(every_chunks=3, min_observations=64.0)

        def run(group_chunks, sizes):
            srt = RT.StreamRuntime(
                cfg, model, specs=specs,
                rt=RT.RuntimeConfig(chunk_size=200, refresh=rcfg,
                                    group_chunks=group_chunks))
            s = 0
            for sz in sizes:
                srt.push(RT.slice_events(ev, s, min(s + sz, N_EVENTS)))
                s += sz
            srt.flush()
            return srt

        sizes = [130, 470, 900, 57, 443]   # ragged, refresh-unaligned
        grouped = run(4, sizes)
        serial = run(1, [N_EVENTS])
        _assert_tree_equal(serial.carry, grouped.carry,
                           "grouped+ragged vs serial with refresh")
        assert [c.refreshed for c in grouped.telemetry.chunks] \
            == [c.refreshed for c in serial.telemetry.chunks]
        assert grouped.refresh_state.refresh_count \
            == serial.refresh_state.refresh_count > 0


class TestRefresh:
    def test_refresh_updates_tables_and_latency_model(self, setup):
        specs, cfg, model, make_events = setup
        rcfg = RT.RefreshConfig(every_chunks=2, min_observations=64.0)
        model_w = RT.prepare_model(specs, model, rcfg)
        assert model_w.ut_tables.shape[1] == RT.table_width(specs, 64)
        carry, _ = eng.run_engine(cfg, model_w, make_events(0),
                                  eng.init_carry(cfg))
        state = RT.RefreshState()
        m2, carry2, did = RT.refresh_model(specs, cfg, model_w, carry,
                                           rcfg, state, RT.TelemetryLog())
        assert did and state.refresh_count == 1
        # shapes stable (no chunk retrace), contents updated
        assert m2.ut_tables.shape == model_w.ut_tables.shape
        assert not np.array_equal(np.asarray(m2.ut_tables),
                                  np.asarray(model_w.ut_tables))
        assert not np.array_equal(np.asarray(m2.f_model.a),
                                  np.asarray(model_w.f_model.a))

    def test_min_observation_gate(self, setup):
        specs, cfg, model, _ = setup
        rcfg = RT.RefreshConfig(min_observations=1e12)
        state = RT.RefreshState()
        _, _, did = RT.refresh_model(specs, cfg, model,
                                     eng.init_carry(cfg), rcfg, state,
                                     RT.TelemetryLog())
        assert not did and state.skipped_obs == 1

    def test_drift_gate_skips_stable_stream(self, setup):
        specs, cfg, model, make_events = setup
        rcfg = RT.RefreshConfig(min_observations=64.0, drift_threshold=1e9)
        carry, _ = eng.run_engine(cfg, model, make_events(0),
                                  eng.init_carry(cfg))
        state = RT.RefreshState()
        log = RT.TelemetryLog()
        _, _, did1 = RT.refresh_model(specs, cfg, model, carry, rcfg, state,
                                      log)
        _, _, did2 = RT.refresh_model(specs, cfg, model, carry, rcfg, state,
                                      log)
        assert did1 and not did2 and state.skipped_drift == 1

    def test_runtime_refreshes_on_cadence(self, setup):
        specs, cfg, model, make_events = setup
        srt = RT.StreamRuntime(
            cfg, model, specs=specs,
            rt=RT.RuntimeConfig(
                chunk_size=500,
                refresh=RT.RefreshConfig(every_chunks=2,
                                         min_observations=64.0)))
        srt.push(make_events(0), flush=True)
        assert srt.refresh_state.refresh_count >= 1
        assert srt.telemetry.aggregate()["refreshes"] >= 1

    def test_refresh_requires_gather_stats(self, setup):
        specs, cfg, model, _ = setup
        no_stats = dataclasses.replace(cfg, gather_stats=False)
        with pytest.raises(ValueError, match="gather_stats"):
            RT.StreamRuntime(
                no_stats, model, specs=specs,
                rt=RT.RuntimeConfig(refresh=RT.RefreshConfig()))


class TestLongStreamGuards:
    """Unbounded streams cross int32 boundaries (~2.1B events)."""

    def test_wrap_event_index_past_int32(self):
        assert int(eng.wrap_event_index(7)) == 7
        assert int(eng.wrap_event_index(2**31 + 5)) == -(2**31) + 5
        # int32 difference arithmetic survives the wrap
        i = eng.wrap_event_index(2**31 + 5)
        open_idx = eng.wrap_event_index(2**31 - 5)
        assert int(i - open_idx) == 10

    def test_refit_handles_wrapped_lat_ptr(self, setup):
        _, cfg, _, _ = setup
        carry = eng.init_carry(cfg, lat_capacity=64)
        carry = carry._replace(
            lat_ptr=jnp.int32(-100),  # ring long since full, ptr wrapped
            lat_samples_n=jnp.arange(64, dtype=jnp.float32),
            lat_samples_l=jnp.arange(64, dtype=jnp.float32) * 1e-4)
        f = RT.refit_latency_model(carry)
        assert np.isfinite(float(f.a)) and float(f.a) > 0


class TestChunkerBudget:
    """suggested_group_chunks: the 8192-event budget is a CAP (regression:
    chunk sizes 513–1023 used to hit the max(16, ...) floor and dispatch
    up to ~16k events, double the documented budget)."""

    @pytest.mark.parametrize("chunk,expect", [
        (256, 32), (512, 16),    # exact divisors of the budget
        (513, 15), (767, 10), (1023, 8),  # the formerly-broken band
        (1024, 16), (4096, 16),  # legacy fixed group, budget-exempt
    ])
    def test_boundary_sizes(self, chunk, expect):
        assert RT.chunker.suggested_group_chunks(chunk) == expect

    def test_budget_is_a_cap_below_1024(self):
        budget = RT.chunker.GROUP_EVENT_BUDGET
        for chunk in range(1, 1024):
            g = RT.chunker.suggested_group_chunks(chunk)
            assert g >= 1
            assert chunk * g <= budget, \
                f"chunk={chunk}: dispatch {chunk * g} exceeds budget"

    def test_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            RT.chunker.suggested_group_chunks(0)


class TestTelemetry:
    def test_chunk_stats_consistent(self, setup):
        _, cfg, model, make_events = setup
        srt = RT.StreamRuntime(cfg, model,
                               rt=RT.RuntimeConfig(chunk_size=512))
        stats = srt.push(make_events(0), flush=True)
        assert [s.n_events for s in stats] == [512, 512, 512, 464]
        assert [s.start for s in stats] == [0, 512, 1024, 1536]
        for s in stats:
            assert s.n_events / s.wall_s > 0 and s.l_e_p99 >= s.l_e_p50
        agg = srt.telemetry.aggregate()
        assert agg["n_events"] == N_EVENTS
        assert agg["pms_shed"] == pytest.approx(float(srt.carry.pms_shed))
        assert agg["completions"] == pytest.approx(
            float(np.asarray(srt.carry.complex_count).sum()))

    def test_quantiles_on_very_short_chunks_match_numpy(self, setup):
        """device_chunk_stats p50/p99 on 1–3 valid events, pinned against
        NumPy percentiles — the quantile must reduce over exactly the
        chunk's valid rows, never padding (regression: satellite audit of
        short-tail chunks)."""
        from repro.runtime import telemetry as TM
        _, cfg, model, make_events = setup
        ev = make_events(0)
        carry, outs = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        l_e = np.asarray(outs.l_e)
        for k in (1, 2, 3):
            piece = jax.tree.map(lambda x: x[:k], outs)
            vec = np.asarray(TM.device_chunk_stats(piece, carry))
            np.testing.assert_allclose(
                vec[TM._VEC["l_e_p50"]], np.percentile(l_e[:k], 50),
                rtol=1e-6, err_msg=f"p50, k={k}")
            np.testing.assert_allclose(
                vec[TM._VEC["l_e_p99"]], np.percentile(l_e[:k], 99),
                rtol=1e-6, err_msg=f"p99, k={k}")
            assert vec[TM._VEC["l_e_max"]] == l_e[:k].max()

    def test_grouped_dispatch_and_ragged_tail_quantiles(self, setup):
        """Grouped dispatches only ever carry FULL chunks (push_region) and
        the short tail runs as its own piece, so per-chunk p50/p99 must
        equal NumPy percentiles over each chunk's exact event span — here
        the tail is 2 events."""
        from repro.runtime import telemetry as TM  # noqa: F401
        _, cfg, model, make_events = setup
        n = 4 * 256 + 2
        ev = make_events(0, n=n)
        _, o_mono = eng.run_engine(cfg, model, ev, eng.init_carry(cfg))
        l_e = np.asarray(o_mono.l_e)
        srt = RT.StreamRuntime(cfg, model, rt=RT.RuntimeConfig(
            chunk_size=256, group_chunks=4))
        stats = srt.push(ev, flush=True)
        assert [s.n_events for s in stats] == [256] * 4 + [2]
        for s in stats:
            span = l_e[s.start:s.start + s.n_events]
            np.testing.assert_allclose(s.l_e_p50, np.percentile(span, 50),
                                       rtol=1e-6, err_msg=f"p50@{s.start}")
            np.testing.assert_allclose(s.l_e_p99, np.percentile(span, 99),
                                       rtol=1e-6, err_msg=f"p99@{s.start}")


class TestDriftingStreams:
    def test_gen_stock_drift_ramps_match_probability(self):
        raw = streams.gen_stock_drift(20_000, p_class=0.01, p_class_end=0.2,
                                      seed=3)
        head = raw.attr[:5000].mean()
        tail = raw.attr[-5000:].mean()
        assert tail > 3 * head

    def test_drifting_arrivals_ramp(self):
        arr = streams.drifting_arrivals(1000, rate=100.0, rate_end=400.0)
        assert np.all(np.diff(arr) > 0)
        assert np.diff(arr)[:10].mean() > 3 * np.diff(arr)[-10:].mean()
        assert arr[0] == 0.0

    def test_classify_rate_end_plumbs(self, setup):
        specs, _, _, _ = setup
        raw = streams.gen_stock(500, seed=0)
        flat = streams.classify(specs, raw, rate=100.0)
        ramp = streams.classify(specs, raw, rate=100.0, rate_end=400.0)
        assert float(ramp.arrival[-1]) < float(flat.arrival[-1])


class TestSatelliteFixes:
    def test_lb_violations_counts_only_bound_exceedances(self):
        r = eng.RunResult(
            complex_count=np.ones(1), pms_created=np.ones(1), pms_shed=0.0,
            shed_calls=0.0, overflow=0.0, ebl_dropped=0.0,
            l_e=np.array([0.1, 0.5, 1.5, 2.0]), n_pm=np.zeros(4),
            carry=None)
        res = runner.ExperimentResult(
            shedder="pspice", fn=0.0, match_probability=1.0, max_rate=1.0,
            result=r, ground_truth=r, latency_bound=1.0)
        assert res.lb_violations == pytest.approx(0.5)
        res2 = dataclasses.replace(res, latency_bound=0.3)
        assert res2.lb_violations == pytest.approx(0.75)

    def test_scheduler_metrics_linear_pass_matches_bruteforce(self):
        from repro.serving import scheduler as SC
        cfg = SC.SchedulerConfig(max_slots=8, slo=0.5, policy="pspice",
                                 seed=0)
        reqs = SC.synth_workload(200, rate=60.0, cfg=cfg, seed=1)
        sched = SC.PSpiceScheduler(cfg)
        for r in sorted(reqs, key=lambda r: r.arrival):
            sched.submit(r)
        while len(sched.finished) < len(reqs):
            sched.run_step()
        m = sched.metrics()
        hit = [r for r in sched.finished
               if r.done and r.finish_time <= r.deadline]
        miss_w = sum(r.weight for r in sched.finished
                     if not (r.done and r.finish_time <= r.deadline))
        assert m["in_slo"] == len(hit)
        assert m["goodput"] == pytest.approx(
            len(hit) / max(len(sched.finished), 1))
        assert m["weighted_miss"] == pytest.approx(
            miss_w / sum(r.weight for r in sched.finished))
        assert m["completed"] == sum(r.done for r in sched.finished)
