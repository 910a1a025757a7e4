"""The client step's readers on hand-made runs: the step program's share
of its HBM roofline, with the error where the traced rounds trained and
no step program was found by name, and the device memory in use as the
silos' training ends; and the FEMNIST configuration's FLOPs and step
bytes against XLA's cost analysis at small widths on the CPU."""
import copy
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import common  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
CFG, MODULE = common.config("femnist_cnn")


def _traced(modules, folded=True):
    record = SimpleNamespace(fold_times_s=[0.1] * 5 if folded else [])
    return SimpleNamespace(
        cfg=CFG, module=MODULE, peaks=PEAKS,
        trace={"window_ns": (0.0, 10e9), "modules": {"/device:TPU:0": modules},
               "rounds": [SimpleNamespace(record=record)]})


def test_step_roofline_reads_the_step_programs():
    least_ns = MODULE.step_bytes(CFG) / PEAKS["hbm_bytes_per_s"] * 1e9
    # Three steps, each twice its least time, beside a fold program.
    steps = [("jit_train_step", i * 1e8, 2 * least_ns) for i in range(3)]
    run = _traced(steps + [("jit__accum_tree_impl", 5e9, 1e6)])
    assert common.metric("step_hbm_roofline").read(run) == pytest.approx(50.0)


def test_step_roofline_at_the_least_time_reads_100():
    least_ns = MODULE.step_bytes(CFG) / PEAKS["hbm_bytes_per_s"] * 1e9
    run = _traced([("jit_train_step", 0.0, least_ns)])
    share = common.metric("step_hbm_roofline").read(run)
    assert share == pytest.approx(100.0) and share <= 100.0 + 1e-9


def test_step_bytes_bound_the_step_not_its_flops():
    flops = 3 * MODULE.forward_flops(CFG) * CFG["batch_size"]
    assert flops / PEAKS["bf16_flops_per_s"] < MODULE.step_bytes(CFG) / PEAKS["hbm_bytes_per_s"]
    assert MODULE.step_bytes(CFG) == pytest.approx(16 * 164_187_070)


def test_step_roofline_fails_loudly_when_no_step_program_matches():
    run = _traced([("jit_step_renamed", 0.0, 1e6)])
    with pytest.raises(RuntimeError):
        common.metric("step_hbm_roofline").read(run)
    assert common.metric("step_hbm_roofline").read(_traced([], folded=False)) is None


def test_step_roofline_needs_a_trace():
    run = _traced([])
    run.trace = None
    assert common.metric("step_hbm_roofline").read(run) is None


def _span(name):
    return SimpleNamespace(name=name)


def _round(n_silos, counters):
    spans = [_span("fl.round")] + [_span("fl.train") for _ in range(n_silos)]
    return SimpleNamespace(record=SimpleNamespace(spans=spans, counters=counters))


def test_train_hbm_share_is_the_mean_over_silo_rounds():
    # Five silos a round; their bytes in use sum to 5 x 8 GB, then 5 x 12 GB.
    run = SimpleNamespace(peaks=PEAKS, rounds=[
        _round(5, {"hbm_in_use_bytes": 40e9}), _round(5, {"hbm_in_use_bytes": 60e9})])
    assert common.metric("train_hbm_share").read(run) == pytest.approx(62.5)


def test_train_hbm_share_is_none_without_the_counter():
    run = SimpleNamespace(peaks=PEAKS, rounds=[_round(5, {"step_calls": 10.0})])
    assert common.metric("train_hbm_share").read(run) is None
    bare = SimpleNamespace(peaks=PEAKS, rounds=[SimpleNamespace(record=SimpleNamespace())])
    assert common.metric("train_hbm_share").read(bare) is None


def _small():
    cfg = copy.deepcopy(CFG)
    cfg["model"].update(n_fc=2, fc_width=64)
    return cfg


def _cost(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    return ca[0] if isinstance(ca, list) else ca


def test_femnist_forward_flops():
    cfg = _small()
    p = MODULE.init_params(cfg, jax.random.PRNGKey(0))
    x = jnp.zeros((1, 28, 28, 1), jnp.float32)
    counted = _cost(lambda p, x: MODULE.ref_logits(cfg, p, x), p, x)["flops"]
    # XLA leaves out the taps on SAME padding (13% of the first conv at
    # 28x28, 26% of the second at 14x14) and adds biases, ReLUs, pools.
    assert MODULE.forward_flops(cfg) == pytest.approx(counted, rel=0.25)
    assert MODULE.forward_flops(CFG) == pytest.approx(349.5e6, rel=1e-3)


def test_femnist_step_bytes_are_at_most_what_xla_counts():
    """The least bytes of a step stay under XLA's count of the compiled
    step (which adds the gradients' round trips and the activations), so
    the share they give cannot read above 100% for want of bytes."""
    from repro.federated import client

    cfg = _small()
    parts = MODULE.program_parts(cfg)
    p = MODULE.init_params(cfg, jax.random.PRNGKey(0))
    state = parts["optimizer"].init(p)
    batch = (jnp.zeros((10, 28, 28, 1), jnp.float32), jnp.zeros((10,), jnp.int32))
    ca = client._STEP.lower(parts["loss_fn"], parts["optimizer"], p, state, batch).compile()
    ca = ca.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert MODULE.step_bytes(cfg) <= ca["bytes accessed"]
    assert MODULE.n_params(cfg) == sum(l.size for l in jax.tree.leaves(p))
