# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (us_per_call = wall time per processed event/request for the
# benchmark; derived = the figure's headline metric) and dumps full row data
# to results/paper_figures.json.
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks import figures
from repro import compile_cache


def _emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.2f},{derived}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced grids (CI-sized)")
    ap.add_argument("--only", default=None,
                    help="comma-separated figure names")
    ap.add_argument("--out", default="results/paper_figures.json")
    args = ap.parse_args(argv)
    compile_cache.enable()

    figs = {
        "fig5": figures.fig5_match_probability,
        "fig6": figures.fig6_event_rate,
        "fig7": figures.fig7_latency_bound,
        "fig8": figures.fig8_processing_time,
        "fig9": figures.fig9_overhead,
        "serving": figures.serving_shed,
    }
    if args.only:
        names = args.only.split(",")
        figs = {k: v for k, v in figs.items() if k in names}

    all_rows = {}
    print("name,us_per_call,derived")
    for name, fn in figs.items():
        t0 = time.time()
        rows = fn(quick=args.quick)
        wall = time.time() - t0
        all_rows[name] = rows
        n_units = max(len(rows), 1)
        if name in ("fig5", "fig6"):
            for r in rows:
                tag = f"{r['figure']}:{r['query']}:{r['shedder']}"
                xk = "window_size" if "window_size" in r else (
                    "pattern_size" if "pattern_size" in r else "rate_pct")
                _emit(f"{tag}:{xk}={r[xk]}",
                      1e6 * r["wall_s"] / 60_000,
                      f"FN%={r['fn_pct']} matchP={r['match_prob']}")
        elif name == "fig7":
            for r in rows:
                _emit(f"fig7:{r['rate']}", 1e6 * r["wall_s"] / 60_000,
                      f"max_l_e={r['max_l_e']} viol={r['violation_frac']}")
        elif name == "fig8":
            for r in rows:
                _emit(f"fig8:{r['variant']}:tau={r['tau_factor']}",
                      1e6 * r["wall_s"] / 60_000, f"FN%={r['fn_pct']}")
        elif name == "fig9":
            for r in rows:
                if r["figure"] == "fig9a":
                    _emit(f"fig9a:{r['shedder']}:ws={r['window_size']}",
                          0.0, f"overhead%={r['overhead_pct']}")
                else:
                    _emit(f"fig9b:ws={r['window_size']}", 0.0,
                          f"model_build_s={r['model_build_s']}")
        elif name == "serving":
            for r in rows:
                _emit(f"serving:{r['policy']}:rate={r['rate']}",
                      1e6 * r["wall_s"] / max(r["completed"], 1),
                      f"goodput={r['goodput']}")
        print(f"# {name} total wall: {wall:.1f}s", file=sys.stderr)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(all_rows, f, indent=1)
    print(f"# wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
