"""Multi-tenant streaming runtime demo: L tenants, drifting streams,
online model refresh (~1 min).

Each tenant is an independent Q1 stock query over its own stream — its
own arrival rate (all drifting upward) and its own drifting match
statistics.  The runtime ingests lane-stacked micro-batches, runs all
lanes through one lane-batched chunk scan with a donated carry, and
between chunks re-estimates every lane's Markov/utility model from its
accumulated observations, so each tenant's shedder tracks its own drift.

  PYTHONPATH=src python examples/runtime_multitenant.py
"""
import sys

from repro.cep import engine as eng
from repro.cep import patterns as pat
from repro.cep import runner
from repro.data import streams
from repro import runtime as RT

COST = dict(c_base=3e-4, c_match=6e-5, c_shed_base=1.5e-4, c_shed_pm=1.5e-6,
            c_ebl=6e-5)


def main() -> int:
    L, n, chunk = 4, 16_384, 1024
    print(f"=== repro.runtime: {L} tenants x {n} events, "
          f"chunk={chunk}, refresh every 4 chunks ===")
    specs = [pat.make_q1(window_size=400, num_symbols=4)]
    cp = pat.compile_patterns(specs)
    cfg = runner.default_config(cp, max_pms=128, latency_bound=0.02,
                                gather_stats=True, shedder="pspice", **COST)
    model = eng.make_model(cp, cfg)

    # Start near capacity and drift well past it: the back half of every
    # stream overloads the operator, so the shedder has to work.
    rate = 1.0 / (cfg.c_base + cfg.c_match * 0.3 * cfg.max_pms)
    evs = []
    for lane in range(L):
        raw = streams.gen_stock_drift(n, num_symbols=50, pattern_symbols=4,
                                      p_class=0.03, p_class_end=0.10,
                                      seed=100 + lane)
        evs.append(streams.classify(specs, raw, rate=rate * (1 + 0.2 * lane),
                                    rate_end=4.0 * rate, seed=lane))

    mt = RT.MultiTenantRuntime(
        cfg, RT.broadcast_model(model, L), num_lanes=L, specs=specs,
        rt=RT.RuntimeConfig(
            chunk_size=chunk,
            refresh=RT.RefreshConfig(every_chunks=4, min_observations=256,
                                     decay=0.5)))

    print(f"\n{'chunk':>5s} {'events/s':>10s} {'p99 l_e':>9s} "
          f"{'PMs shed':>9s} {'completions':>12s} {'refresh':>8s}")
    # Stream in pushes of an odd size — the buffer re-chunks; flush drains
    # the tail.
    push = 3000
    evL = RT.stack(evs)
    for s in range(0, n, push):
        batch = RT.slice_events(evL, s, min(s + push, n), axis=1)
        for st in mt.push(batch, flush=(s + push >= n)):
            print(f"{st.chunk_index:5d} {st.n_events / st.wall_s:10.0f} "
                  f"{st.l_e_p99:9.4f} {st.pms_shed:9.0f} "
                  f"{st.completions:12.0f} "
                  f"{'yes' if st.refreshed else '':>8s}")

    agg = mt.telemetry.aggregate()
    merged = mt.merged_carry()
    rate = agg["n_events"] / agg["wall_s"]
    print(f"\naggregate: {rate:.0f} events/s over "
          f"{agg['n_events']} events in {agg['n_chunks']} chunks; "
          f"{agg['refreshes']} refresh rounds")
    print("per-tenant completions:",
          [int(c) for c in merged.complex_count])
    print("per-tenant refreshes:  ",
          [s.refresh_count for s in mt.refresh_state])
    return 0


if __name__ == "__main__":
    sys.exit(main())
