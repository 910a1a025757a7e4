"""The trace reduction, on a small synthetic trace."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import trace as tr  # noqa: E402

# Two programs on one device: "jit_step" with two ops, then "jit_fold"
# with one; times in ns.  Busy: [10, 40) and [60, 70) -> 40 ns of 100.
MODULES = [("jit_step", 10.0, 30.0), ("jit_fold", 60.0, 10.0)]
OPS = [
    ("%fusion.1 = f32[8]{0} fusion(%p)", 10.0, 20.0),
    ("%fusion.2 = f32[8]{0} fusion(%fusion.1)", 25.0, 15.0),   # overlaps the first
    ("%dequant_fold.1 = f32[2,8192]{1,0} custom-call(%a, %b)", 60.0, 10.0),
]
WINDOW = (0.0, 100.0)


def test_union_merges_overlaps():
    assert tr.union([(5, 10), (0, 3), (2, 4), (10, 12)]) == [(0, 4), (5, 12)]


def test_busy_and_idle_share():
    busy = tr.busy_ns(OPS, WINDOW)
    assert busy == pytest.approx(40.0)
    assert 1 - busy / (WINDOW[1] - WINDOW[0]) == pytest.approx(0.6)


def test_busy_is_clipped_to_the_window():
    assert tr.busy_ns(OPS, (30.0, 65.0)) == pytest.approx(10.0 + 5.0)


def test_gaps_cover_the_idle_time():
    gaps = tr.gaps(OPS, WINDOW)
    assert gaps == [(0.0, 10.0), (40.0, 60.0), (70.0, 100.0)]
    assert sum(e - s for s, e in gaps) == pytest.approx(60.0)


def test_kernel_time_and_count():
    pattern = r"^%dequant_fold[\w.]* = .*custom-call\("
    assert tr.time_ns(OPS, pattern, WINDOW) == pytest.approx(10.0)
    assert tr.count(OPS, pattern, WINDOW) == 1
    assert tr.time_ns(MODULES, r"^jit_fold$", WINDOW) == pytest.approx(10.0)


def test_roofline_share_takes_the_larger_bound():
    # 1 GB at 1 GB/s is 1 s; 1 GFLOP at 1 TFLOP/s is 1 ms: memory bound.
    share = tr.roofline_share(2e9, flops=1e9, nbytes=1e9, peak_flops=1e12, peak_bytes_s=1e9)
    assert share == pytest.approx(50.0)
    share = tr.roofline_share(2e9, flops=4e12, nbytes=1e9, peak_flops=1e12, peak_bytes_s=1e9)
    assert share == pytest.approx(200.0)   # counted too high shows above 100
    assert tr.roofline_share(0.0, 1.0, 1.0, 1.0, 1.0) is None


def test_op_table_names_ops_by_program():
    table = dict((k, v) for k, v in tr.op_table(OPS, MODULES, WINDOW))
    assert table["jit_step/fusion.1"] == pytest.approx(20e-9)
    assert table["jit_fold/dequant_fold.1"] == pytest.approx(10e-9)
    assert list(table)[0] == "jit_step/fusion.1"


def test_gap_table_attributes_gaps_to_phases():
    phases = [("dispatch", 0.0, 10.0), ("silo compute", 10.0, 50.0), ("fold", 50.0, 70.0)]
    table = tr.gap_table(tr.gaps(OPS, WINDOW), phases)
    assert table[0] == ["between rounds", pytest.approx(30e-9)]
    assert table[1] == ["fold", pytest.approx(20e-9)]      # middle of (40, 60) is 50
    assert table[2] == ["dispatch", pytest.approx(10e-9)]
