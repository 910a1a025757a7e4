"""Runs with the timed path broken underneath come out not correct: a
silo's training that returns the weights unchanged, a compiled step that
returns its state unchanged, a step that sees half of each batch (the
mean taken over the rest), and one silo's update altered where it is
produced; tiny widths on the CPU, the cells' own limits."""
import dataclasses
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from bench_tiny import CELLS  # noqa: E402


def _trained_as(transform):
    """Patch FLClient.train so that its result's weights are
    ``transform(client, global_params, trained_params)``."""
    from repro.federated import FLClient

    train = FLClient.train

    def broken(self, global_params):
        r = train(self, global_params)
        return dataclasses.replace(r, params=transform(self, global_params, r.params))
    return broken


def _unchanged(client, base, trained):
    return base


def _altered(client, base, trained):
    if not client.client_id.endswith("_0"):
        return trained
    return jax.tree.map(lambda b, t: b + 2.0 * (t - b), base, trained)


@pytest.mark.parametrize("workload", sorted(CELLS))
@pytest.mark.parametrize("fault", ["unchanged", "step_unchanged", "half_batch", "altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    from repro.federated import FLClient

    if fault == "unchanged":
        monkeypatch.setattr(FLClient, "train", _trained_as(_unchanged))
    elif fault == "altered":
        monkeypatch.setattr(FLClient, "train", _trained_as(_altered))
    elif fault == "step_unchanged":   # the compiled step hands its state back
        init = FLClient.__init__

        def frozen(self, *a, **k):
            init(self, *a, **k)
            step = self._train_step

            def same(params, opt_state, batch):
                return params, opt_state, step(params, opt_state, batch)[2]
            self._train_step = same
        monkeypatch.setattr(FLClient, "__init__", frozen)
    else:   # the step sees half of each batch: the mean over the rest
        init = FLClient.__init__

        def half(self, *a, **k):
            init(self, *a, **k)
            self.batch_fn = lambda b: tuple(x[: max(1, len(x) // 2)] for x in b)
        monkeypatch.setattr(FLClient, "__init__", half)
    out = bench_tiny.run(monkeypatch, workload)
    assert not out["correct"], out["checks"]


