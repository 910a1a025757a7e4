"""The check that decides ``correct``: a sound run passes and the bf16
control does not, at small sizes on the CPU with the cells' own limits
(readings at the cells' sizes are in PERF.md)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from bench import calibrate, common  # noqa: E402
from bench_tiny import CELLS  # noqa: E402


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(monkeypatch, workload):
    out = bench_tiny.run(monkeypatch, workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= len(
        bench_tiny.config(CELLS[workload][0])[0]["silos"]["train"])
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_is_not_correct(workload):
    cfg, module = bench_tiny.config(CELLS[workload][0], bench_tiny.CONTROL)
    codec = common.traffic(CELLS[workload][1])["compression"]
    limits = common.cell(workload)["limits"]
    got = calibrate.readings(cfg, module, codec, 2**31 + 23, ["control"])
    assert any(got["control"][k] > v for k, v in limits.items()), got["control"]


def test_half_batch_reading_fails_the_shakespeare_limit():
    cfg, module = bench_tiny.config("shakespeare_lstm")
    limits = common.cell("shakespeare_dense")["limits"]
    got = calibrate.readings(cfg, module, None, 2**31 + 29, ["half_batch"])
    assert got["half_batch"]["change_gap"] > limits["change_gap"], got["half_batch"]


def test_compare_steps_reads_the_first_steps_gaps():
    from bench import fl_reference as flr

    ref = {"loss": [0.7, 0.0, 0.0], "grad": [2.0, 1.0, 1e-9], "change": [0.3, 0.1, 0.2]}
    same = flr.compare_steps([ref], [ref])
    assert all(v == 0.0 for v in same.values()), same
    unmoved = flr.compare_steps([{"loss": [0.7, 0.7, 0.7], "grad": [0.0, 0.0, 0.0],
                                  "change": [0.0, 0.0, 0.0]}], [ref])
    # The third leaf's gradient is under GRAD_FLOOR of the median: left out.
    assert unmoved["grad_gap"] == 1.0 and unmoved["step_change_gap"] == 1.0, unmoved
    assert unmoved["first_loss_gap"] == 0.0 and unmoved["step_loss_gap"] == 1.0, unmoved
    short = flr.compare_steps([{**ref, "loss": ref["loss"][:2]}], [ref])
    assert short["first_loss_gap"] == float("inf"), short
