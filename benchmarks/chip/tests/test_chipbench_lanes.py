"""Tenant lanes through the harness, added by files alone: the fixture
cell (four tenants of Q1) runs through ``run.py`` in a copied checkout on
one CPU device, the vmapped lane step, with ``correct`` true, and each
planted fault and the two controls make ``correct`` false; and what the
harness refuses of a lane configuration.  The sharded lane step on four
virtual devices is ``test_chipbench_lanes_mesh.py``'s."""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "tests"))

import lane_checkout as LC  # noqa: E402
import run  # noqa: E402
import system as S  # noqa: E402

# Each fault a lane cell can have, and the controls: exactly-once broken
# (duplicate) and tenant isolation broken (lane_swap).  Seed 1 completes
# matches in every toy lane, the last lane unshed (overload 0.8), so that
# a completion counted twice there shows.
FAULTS = ["stuck", "half", "altered", "duplicate", "lane_swap",
          "shed_none", "refresh_skipped", "stale_tables"]
EXACT_READ = ("lost_per_found", "table_gap", "latency_x_bound")


def check_sound(line: dict, chips: int) -> None:
    assert line["correct"] is True, line
    assert line["window"]["lanes"] == 4
    assert line["window"]["devices"] == chips
    # Two toy pushes of 256 events per lane, four lanes.
    assert line["attempted"] == 2 * 256 * 4
    lanes = line["info"][0]["lanes"]
    assert len(lanes) == 4
    # The tenants differ: each lane's stream opens its own windows.
    assert len({ln["opened"] for ln in lanes}) == 4
    assert line["info"][1]["reference"]["opened"] == sum(
        ln["opened"] for ln in lanes)


def check_fault(line: dict) -> None:
    assert line["correct"] is False, line
    lanes = line["info"][0]["lanes"]
    if line["fault"] == "altered":
        # One broken tenant among sound ones: the last.
        broken = [i for i, ln in enumerate(lanes) if any(
            v for k, v in ln["checks"].items() if k not in EXACT_READ)]
        assert broken == [3], lanes


@pytest.fixture(scope="module")
def one_device(tmp_path_factory):
    return LC.readings(tmp_path_factory.mktemp("lanes1"), 1,
                       ["none"] + FAULTS)


def test_lane_fixture_correct_on_one_device(one_device):
    check_sound(one_device["none"], 1)


@pytest.mark.parametrize("fault", FAULTS)
def test_lane_fault_makes_correct_false(one_device, fault):
    check_fault(one_device[fault])


def _config():
    with open(os.path.join(LC.FIXTURES, LC.CONFIG + ".json")) as f:
        return json.load(f)


def test_lane_configs_cycle_the_overrides():
    lanes = S.lane_configs(_config(), 4)
    assert [c["dataset"]["p_class"] for c in lanes] == [0.15, 0.25] * 2
    assert [c["dataset"]["hot_fraction"] for c in lanes] == [
        0.9, 0.95, 0.85, 0.9]
    assert [c["model_build"]["overload"] for c in lanes] == [
        1.4, 1.2, 1.3, 0.8]
    assert all(c["dataset"]["num_symbols"] == 40 for c in lanes)


def _refused(edit, chips=1):
    config = copy.deepcopy(_config())
    edit(config)
    with pytest.raises(ValueError):
        S.lane_configs(config, chips)


REFUSALS = {
    "persist": lambda c: c["served"].update(
        persist={"snapshot_every_chunks": 4, "wal_fsync_every": 1}),
    "lanes_under_match": lambda c: c["served"].update(runtime="match"),
    "unknown_override": lambda c: c["served"]["lanes"]["dataset"].update(
        window=[1, 2]),
    "empty_override": lambda c: c["served"]["lanes"].update(overload=[]),
    "unknown_lane_key": lambda c: c["served"]["lanes"].update(emit=True),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_lane_configuration_refused(what):
    _refused(REFUSALS[what])


def test_lanes_that_the_chips_do_not_divide_refused():
    _refused(lambda c: c["served"]["lanes"].update(count=6), chips=4)


def test_one_lane_runtime_on_four_chips_refused():
    with open(os.path.join(HERE, "configs", "soccer_q3x8.json")) as f:
        config = json.load(f)
    with pytest.raises(ValueError):
        S.lane_configs(config, 4)


def test_emit_metric_for_a_lane_cell_refused():
    bench = {"end_to_end": [{"name": "emit_p50_ms"}], "per_layer": []}
    cell = {"name": LC.CELL, "chips": 1}
    with pytest.raises(ValueError):
        run.lane_cell(bench, cell, _config(), None)


def test_lane_swap_refused_without_lanes(capsys):
    argv = ["--workload", "soccer_q3x8.saturate", "--seed", "1",
            "--seconds", "1", "--trace", "0", "--rehearse", "--fault",
            "lane_swap"]
    assert run.main(argv) != 0
    assert not [ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("{")]
