"""The tenant-lane fixture cell on a four-chip mesh: four virtual CPU
devices, so that the sharded lane step
(``dist/sharding.py::run_chunk_lanes_sharded``) runs under the harness,
one lane to a device.  ``correct`` comes out true, and false for each
planted fault and both controls; ``altered`` breaks the last lane, on the
last device, alone."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(HERE, "tests"))

import lane_checkout as LC  # noqa: E402
from test_chipbench_lanes import FAULTS, check_fault, check_sound  # noqa


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    return LC.readings(tmp_path_factory.mktemp("lanes4"), 4,
                       ["none"] + FAULTS)


def test_lane_fixture_correct_on_a_four_device_mesh(four_devices):
    check_sound(four_devices["none"], 4)


@pytest.mark.parametrize("fault", FAULTS)
def test_sharded_lane_fault_makes_correct_false(four_devices, fault):
    check_fault(four_devices[fault])
