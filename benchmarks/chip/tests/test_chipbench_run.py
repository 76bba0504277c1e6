"""run.py at toy sizes on the CPU: every cell through the whole run, each
planted fault and the control seen to make ``correct`` false, and the
refusals (no TPU; no program beside the benchmark)."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import run  # noqa: E402

with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 77


def _rehearse(capsys, cell, seed=SEED, trace=0, fault=None):
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "60",
            "--trace", str(trace), "--rehearse"]
    if fault:
        argv += ["--fault", fault]
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell, capsys):
    line = _rehearse(capsys, cell)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    got = set(line["metrics"])
    # An open-loop toy window may finish no match: its latencies are then
    # absent, never zero.
    assert got <= want and "setup_s" in got
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # Exact comparisons hold to 0; the quality, table and latency numbers
    # to the limits their readings set.
    assert line["checks"] and all(
        c["limit"] == 0 for k, c in line["checks"].items()
        if k not in ("lost_per_found", "table_gap", "latency_x_bound"))
    assert {"lost_per_found", "table_gap", "latency_x_bound",
            "refresh_missing"} <= set(line["checks"])


def test_traced_rehearsal_reports_host_layers(capsys):
    line = _rehearse(capsys, "stock_q1x3.saturate", trace=1)
    assert line["correct"] is True
    assert {"chunk_wall_ms_per_kev.sat",
            "refresh_ms_per_kev.sat"} <= set(line["metrics"])


# Each fault a cell can have (every cell runs on one chip: there is no
# exchange between chips to leave out), and the control: exactly-once
# broken.  Seed 1 puts a completed match, and a window opened in a
# chunk's second half, inside each toy window, so that every fault has
# something to break.
FAULT_CASES = [(c, f) for c in CELLS
               for f in ("stuck", "half", "altered", "duplicate",
                         "shed_none")]
# A toy rate80 window reaches no refresh after its warm pushes, so a
# refresh skipped or stale tables can only show in the saturating cells.
FAULT_CASES += [(c, f) for c in CELLS if "saturate" in c
                for f in ("refresh_skipped", "stale_tables")]


@pytest.mark.parametrize("cell,fault", FAULT_CASES)
def test_fault_makes_correct_false(cell, fault, capsys):
    line = _rehearse(capsys, cell, seed=1, fault=fault)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def _cmd(cwd, env=None):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    import jax
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _cmd(CHECKOUT, env)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", "out",
                                                  "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _cmd(tmp_path, env)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
