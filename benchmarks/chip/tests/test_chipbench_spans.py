"""The per-layer metrics that read the program's own spans and counters:
a traced toy run of each cell reports every one that lists the cell, and
a program whose chunks carry no spans reads as nothing."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import run  # noqa: E402

with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
SPAN_METRICS = [m for m in BENCH["per_layer"]
                if m["source"] in ("program_span", "program_counter")
                and m["name"] not in ("chunk_wall_ms_per_kev.sat",
                                      "refresh_ms_per_kev.sat")]
# Spans inside ChunkStats.wall_s, so inside chunk_wall_ms_per_kev.sat.
CHUNK_LEVEL = ("device_wait_ms_per_kev.sat", "decode_fetch_ms_per_kev.sat",
               "decode_sets_ms_per_kev.sat")


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_span_metrics(cell, capsys):
    argv = ["--workload", cell, "--seed", str(2**31 + 91), "--seconds",
            "60", "--trace", "1", "--rehearse"]
    assert run.main(argv) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    want = [m["name"] for m in SPAN_METRICS if cell in m["workloads"]]
    assert want
    got = line["metrics"]
    for name in want:
        assert name in got, name
        assert got[name]["value"] >= 0, name
    if "chunk_wall_ms_per_kev.sat" in got:
        inner = sum(got[n]["value"] for n in CHUNK_LEVEL if n in got)
        assert inner <= got["chunk_wall_ms_per_kev.sat"]["value"]


class _Chunk:
    wall_s = refresh_wall_s = 0.01


def test_program_without_spans_reads_nothing():
    rec = run.Record()
    rec.events = 1024
    rec.chunks = [_Chunk(), _Chunk()]
    for m in SPAN_METRICS:
        assert run._reader(m["name"])(rec) is None, m["name"]
