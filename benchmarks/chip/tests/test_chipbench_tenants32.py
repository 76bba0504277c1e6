"""The ``tenants32_q1`` deployment: its configuration is one the lane
harness takes on four chips, and its cell is declared with the files the
harness finds it by and reports the accepted cells' per-layer metrics of
the layers it runs."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import run  # noqa: E402
import system as S  # noqa: E402

CELL = "tenants32_q1.saturate_4chip"
METRICS = ("kernel_ms_per_kev.sat", "block_step_roofline.sat",
           "chunk_wall_ms_per_kev.sat", "refresh_ms_per_kev.sat",
           "refresh_fit_ms_per_kev.sat", "device_idle_share.sat",
           "device_wait_ms_per_kev.sat")


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(CELL)


def test_lane_configs_on_four_chips(cell):
    _, entry, config, _ = cell
    assert entry["chips"] == 4 and entry["config"] == config["name"]
    lanes = S.lane_configs(config, entry["chips"])
    assert len(lanes) == 32
    assert [lc["dataset"]["p_class"] for lc in lanes[:5]] == [
        0.06, 0.08, 0.10, 0.12, 0.06]
    assert [lc["model_build"]["overload"] for lc in lanes[:4]] == [
        1.2, 1.4, 1.6, 1.2]
    for lc in lanes:
        assert lc["queries"] == config["queries"]
        assert lc["engine"] == config["engine"]
        assert {k: v for k, v in lc["dataset"].items() if k != "p_class"} \
            == {k: v for k, v in config["dataset"].items()
                if k != "p_class"}
    # A one-lane runtime could not take it; the lane runtime refuses
    # a chip count that does not divide the lanes.
    with pytest.raises(ValueError):
        S.lane_configs(config, 3)


def test_declared_in_the_benchmark(cell):
    bench, _, config, mix = cell
    assert mix["loop"] == "closed" and mix["push_events"] % \
        config["served"]["chunk"] == 0
    assert {m["name"] for m in run.reported(bench, cell[1], True)} == set(
        METRICS)
    assert {m["name"] for m in run.reported(bench, cell[1], False)} == {
        "events_per_s", "setup_s"}
    for name in METRICS:
        assert os.path.isfile(os.path.join(HERE, "metrics", name + ".py"))
    # The tables are held to a fit of each lane's own observations.
    assert set(config["limits"]) == {"table_gap"}
