#!/usr/bin/env python3
"""Find the highest wall-clock rate a cell's configuration sustains.

    python3 benchmarks/chip/sweep.py --workload stock_q1x3.rate80 \\
        --seed <n> --seconds <s> --rates 4000,6000,8000

One set-up, then the cell's open loop at each rate in turn for
``--seconds``, on the same runtime (the stream goes on).  For each rate it
prints the pushes' lag behind their due times at the start and at the end
of the window: a backlog that grows shows as a lag that grows, by about
``(1 - capacity / rate)`` of the window.  The knee is the highest rate
whose lag does not grow; the cell runs at 0.8 of it, written into its
workload file as a number.  Run once, when the cell is defined.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated events/s, tried in this order")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    run.pin_malloc()
    bench, cell, config, mix = run.load_cell(args.workload)
    sys.path.insert(0, os.path.join(run.CHECKOUT, "src"))
    import jax
    if jax.devices()[0].platform != "tpu":
        return run.fail("the sweep measures the chip; JAX found no TPU")
    run.compile_cache(jax)
    import tempfile
    import system as S
    push = mix["push_events"]
    mix = dict(mix, push_events_scaled=push)
    warm_n = mix["warm_pushes"] * push
    os.makedirs(os.path.join(run.HERE, "out"), exist_ok=True)
    out_dir = tempfile.mkdtemp(prefix="sweep.", dir=os.path.join(run.HERE,
                                                                 "out"))
    system = S.build(config, run.derive_seeds(args.seed),
                     os.path.join(out_dir, "persist"))
    for lo in range(0, warm_n, push):
        system.runtime.push(S.batch(system, lo, lo + push))
    lo = warm_n
    ann = run._annotate(False)
    for r in rates:
        rec, lo, wall = run.window(system, dict(mix, rate_events_per_s=r),
                                   lo, args.seconds, ann, None)
        lag = np.asarray(rec.gen_lags)
        q = max(1, lag.size // 4)
        print(json.dumps({
            "rate": r, "pushes": int(lag.size),
            "achieved_events_per_s": rec.events / wall,
            "lag_first_quarter_ms": float(lag[:q].mean() * 1e3),
            "lag_last_quarter_ms": float(lag[-q:].mean() * 1e3),
            "push_wall_p50_ms": float(np.percentile(
                [b - a for a, b in rec.push_spans], 50) * 1e3),
            "emit_p50_ms": float(np.percentile(rec.latencies, 50) * 1e3)
            if rec.latencies else None,
            "emit_p99_ms": float(np.percentile(rec.latencies, 99) * 1e3)
            if rec.latencies else None,
            "matches": len(rec.latencies)}), flush=True)
    import shutil
    shutil.rmtree(out_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
