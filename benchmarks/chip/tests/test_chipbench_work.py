"""The block kernel's byte count against a count by hand, and the peaks."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import work as WK  # noqa: E402


def test_stock_shape_by_hand():
    # stock_q1x3: P=3 queries, N=128 slots, M=11 states, A=11, W=32
    # events per block, B=38 utility bins; SEQ, emitting, gathering stats,
    # pSPICE.
    got = WK.block_step_bytes(P=3, N=128, M=11, A=11, W=32, B=38,
                              any_kind=False, emit=True, stats=True,
                              pspice=True)
    slots = 3 * 128
    store = slots * 16                      # active, state, open, bind
    events_in = 32 * (3 * 3 * 4 + 16)       # class/bind/open per query + 4
    rows_out = 32 * 16
    emitted = 32 * slots * 8                # open and bind per slot, event
    stats = 2 * 2 * 3 * 11 * 11 * 4         # counts and rewards, in and out
    tables = 3 * 38 * 11 * 4
    lat = 32 * 8
    assert got == (2 * store + events_in + rows_out + emitted + stats
                   + tables + lat)


def test_any_planes_counted():
    base = dict(P=8, N=128, M=11, A=11, W=32, B=3, emit=False,
                stats=True, pspice=True)
    seq = WK.block_step_bytes(any_kind=False, **base)
    any_ = WK.block_step_bytes(any_kind=True, **base)
    assert any_ - seq == 2 * 8 * 128 * 11 * 4


def test_roofline_takes_the_larger_bound():
    # One bound only: the vector unit has no published peak, so the share
    # is the HBM time of the counted bytes over the kernel's time.
    pk = WK.peaks("TPU v5 lite")
    r = WK.roofline(int(819e9 / 100), events=32 * 100, W=32, kernel_s=2.0,
                    pk=pk)
    assert r["share_pct"] == pytest.approx(50.0)     # 1 s of 2 s
    assert set(r) == {"bytes", "share_pct"}


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        WK.peaks("TPU v4")
