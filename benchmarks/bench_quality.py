"""Quality-of-results benchmark: the paper-figure sweep + the CI gate.

Runs ``repro.eval.sweep.run_quality_sweep`` — {stock, soccer, bus} ×
{pspice, PM-BL, E-BL} × overload levels on the seeded scenario registry
— and writes:

  BENCH_quality.json        the full grid + the headline table
  results/quality_<ds>.json per-dataset grids incl. degradation curves

Gate (--check): the run FAILS (exit 1) unless the paper's headline
ordering holds — pSPICE's match-set false-negative ratio ≤ PM-BL's and
≤ E-BL's on EVERY dataset at the paper overload level (120%).  Unlike
the throughput benchmarks this gate needs no machine normalization: FN
ratios are determined by the seeded streams and the simulated-time
model, not by wall-clock speed, so --quick CI runs reproduce them
exactly.

Usage:  PYTHONPATH=src python benchmarks/bench_quality.py
            [--quick] [--check] [--out BENCH_quality.json]
            [--results-dir results]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

from repro import compile_cache
from repro.eval import sweep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="short streams (the per-PR CI configuration)")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless the headline ordering holds")
    ap.add_argument("--out", default="BENCH_quality.json")
    ap.add_argument("--results-dir", default=None,
                    help="also write per-dataset quality_<ds>.json here")
    args = ap.parse_args(argv)
    compile_cache.enable()

    bench = sweep.run_quality_sweep(quick=args.quick,
                                    results_dir=args.results_dir)

    pathlib.Path(args.out).write_text(
        json.dumps(bench, indent=2, sort_keys=True) + "\n")

    print(f"headline (overload x{bench['config']['headline_level']:g}, "
          f"match-set FN ratio vs no-shed ground truth):")
    for ds, cells in bench["headline"].items():
        cols = "  ".join(f"{sh}={fn:.4f}" for sh, fn in cells.items())
        print(f"  {ds:8s} {cols}")
    if bench["violations"]:
        for v in bench["violations"]:
            print(f"VIOLATION: {v}")
    print(f"ordering_ok={bench['ordering_ok']}  -> {args.out}")

    if args.check and not bench["ordering_ok"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
