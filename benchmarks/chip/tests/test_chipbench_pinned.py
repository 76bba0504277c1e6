"""Each cell's toy rehearsal at one seed reads what it read before the
harness took tenant lanes: the checks, the events attempted and the
reference's counts, pinned to the values of the harness that ran one lane
only.  A change to the one-lane path that moves any of them shows here."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import run  # noqa: E402

SEED = 2**31 + 77
PINNED = {
    "stock_q1x3.saturate": {
        "attempted": 512,
        "checks": {
            "events_gap": 0,
            "admit_gap": 0,
            "spawn_gap": 0,
            "store_wrong": 0,
            "unsound_matches": 0,
            "count_excess": 0,
            "loss_unexplained": 0,
            "shed_unexplained": 0,
            "refresh_missing": 0,
            "lost_per_found": 11.0,
            "table_gap": 1.915866210674187e-05,
            "latency_x_bound": 1.0011887550354004
        },
        "reference": {
            "opened": 33,
            "completed": 11,
            "found": 0
        },
        "shed_calls": 16,
        "pms_shed": 33
    },
    "stock_q1x3.rate80": {
        "attempted": 192,
        "checks": {
            "events_gap": 0,
            "admit_gap": 0,
            "spawn_gap": 0,
            "store_wrong": 0,
            "unsound_matches": 0,
            "count_excess": 0,
            "loss_unexplained": 0,
            "shed_unexplained": 0,
            "refresh_missing": 0,
            "lost_per_found": 3.0,
            "table_gap": 2.0726633257655015e-05,
            "latency_x_bound": 1.0015547275543213
        },
        "reference": {
            "opened": 15,
            "completed": 3,
            "found": 0
        },
        "shed_calls": 4,
        "pms_shed": 15
    },
    "soccer_q3x8.saturate": {
        "attempted": 512,
        "checks": {
            "events_gap": 0,
            "spawn_gap": 0,
            "store_wrong": 0,
            "count_excess": 0,
            "loss_unexplained": 0,
            "shed_unexplained": 0,
            "refresh_missing": 0,
            "lost_per_found": 0.31092436974789917,
            "table_gap": 2.7407691964456445e-06,
            "latency_x_bound": 1.000438928604126
        },
        "reference": {
            "opened": 792,
            "completed": 780,
            "found": 595
        },
        "shed_calls": 26,
        "pms_shed": 189
    }
}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_rehearsal_pinned(cell, capsys):
    argv = ["--workload", cell, "--seed", str(SEED), "--seconds", "60",
            "--trace", "0", "--rehearse"]
    assert run.main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    ref = next(json.loads(x) for x in lines if x.startswith('{"reference"'))
    want = PINNED[cell]
    assert last["correct"] is True
    assert last["attempted"] == want["attempted"]
    got = {k: v["value"] for k, v in last["checks"].items()}
    assert list(got) == list(want["checks"])
    for k, v in want["checks"].items():
        if isinstance(v, int):
            assert got[k] == v, k
        else:
            assert got[k] == pytest.approx(v, rel=1e-6), k
    assert {k: ref["reference"][k] for k in want["reference"]} == \
        want["reference"]
    assert (ref["shed_calls"], ref["pms_shed"]) == (want["shed_calls"],
                                                    want["pms_shed"])
