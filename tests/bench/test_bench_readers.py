"""Metric readers on hand-made runs: a p95 with too few samples is left
out, and a fold whose programs are no longer found by name is an error."""
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import common  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _round(index, n_silos, folded=True):
    record = SimpleNamespace(fold_times_s=[0.1] * n_silos if folded else [])
    receipts = {f"silo_{i}": index + 0.01 * (i + 1) for i in range(n_silos)}
    return SimpleNamespace(index=index, start=float(index), record=record, receipts=receipts)


@pytest.mark.parametrize("n_rounds,expect_value", [(24, False), (25, True), (40, True)])
def test_turnaround_p95_needs_200_samples(n_rounds, expect_value):
    run = SimpleNamespace(rounds=[_round(i, 8) for i in range(n_rounds)])
    value = common.metric("silo_turnaround_p95_s").read(run)
    if expect_value:
        assert value == pytest.approx(0.08, abs=0.01)
    else:
        assert value is None


def _traced_run(modules, ops, folded=True):
    window = (0.0, 1e9)
    return SimpleNamespace(
        n_params=1 << 20, peaks=PEAKS,
        trace={"window_ns": window, "modules": {"/device:TPU:0": modules},
               "ops": {"/device:TPU:0": ops}, "rounds": [_round(1, 4, folded)]})


def test_dense_fold_roofline_reads_its_programs():
    run = _traced_run([("jit__accum_tree_impl", 0.0, 1e6), ("jit_step", 2e6, 1e6)], [])
    share = common.metric("fold_roofline.dense").read(run)
    assert 0 < share < 100


def test_dense_fold_roofline_fails_loudly_when_no_program_matches():
    run = _traced_run([("jit_fold_renamed", 0.0, 1e6)], [])
    with pytest.raises(RuntimeError):
        common.metric("fold_roofline.dense").read(run)


def test_dequant_fold_roofline_fails_loudly_when_no_kernel_matches():
    ops = [("%fusion.1 = f32[8]{0} fusion(%p)", 0.0, 1e6)]
    with pytest.raises(RuntimeError):
        common.metric("dequant_fold_roofline").read(_traced_run([], ops))
    assert common.metric("dequant_fold_roofline").read(_traced_run([], ops, folded=False)) is None
