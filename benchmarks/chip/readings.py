#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: many seeds in one process.

    python3 benchmarks/chip/readings.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--faults none,duplicate,shed_all] [--rehearse]

Runs the cell's whole run (``run.main``) once per fault (``none``: a
sound run) and seed in this one process, so that set-up compiles once,
and prints per run the numbers that were compared.  Sound runs give the
lower readings; the control (``duplicate``) and the planted faults give
the upper ones.  The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="none")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend (tests only)")
    args = ap.parse_args(argv)
    for fault, seed in [(f, s) for f in args.faults.split(",")
                        for s in args.seeds.split(",")]:
        argv = ["--workload", args.workload, "--seed", seed, "--seconds",
                str(args.seconds), "--trace", "0"]
        if fault != "none":
            argv += ["--fault", fault]
        if args.rehearse:
            argv.append("--rehearse")
        out, err = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
            rc = run.main(argv)
        lines = out.getvalue().strip().splitlines()
        if rc != 0 or not lines:
            print(json.dumps({"seed": seed, "fault": fault, "rc": rc,
                              "stderr": err.getvalue()[-2000:]}), flush=True)
            continue
        last = json.loads(lines[-1])
        print(json.dumps({
            "seed": seed, "fault": fault, "correct": last["correct"],
            "window": json.loads(lines[0]),
            "attempted": last["attempted"],
            "checks": {k: v["value"] for k, v in last["checks"].items()},
            "info": [json.loads(x) for x in lines[:-1]
                     if x.startswith(('{"reference"', '{"lanes"'))]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
