"""Tiny sizes of the benchmark's configurations, for CPU tests.

Widths are cut here only; the cells run the published ones."""
import copy
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402

from bench import common, harness  # noqa: E402

TINY = {
    "til_vgg16": lambda c: (
        c["model"].update(image_size=16, stages=[[8, 1], [16, 1]], fc_width=32),
        c["silos"].update(train=[48, 48, 40, 48], test=[20, 20, 20, 17])),
    "shakespeare_lstm": lambda c: (
        c["model"].update(hidden=32, seq_len=12),
        c["silos"].update(train=[40, 50, 35], test=[10, 12, 7])),
}


# The bf16 control separates from float32 only where updates pile up on
# wide layers: VGG16 at its published widths on 32x32 images, 10 steps.
CONTROL = {
    "til_vgg16": lambda c: (
        c["model"].update(image_size=32),
        c["silos"].update(train=[160, 160], test=[16, 16])),
    "shakespeare_lstm": TINY["shakespeare_lstm"],
}

_CONFIG = common.config


def config(name, sizes=TINY):
    cfg, module = _CONFIG(name)
    cfg = copy.deepcopy(cfg)
    sizes[name](cfg)
    return cfg, module


# (config, traffic) of each cell in BENCHMARK.json.
CELLS = {w["name"]: (w["config"], w["traffic"]) for w in common.benchmark()["workloads"]}


def run(monkeypatch, workload, seed=2**31 + 17):
    """A run with the look for a chip skipped and tiny widths."""
    monkeypatch.setattr(common, "config", config)
    monkeypatch.setattr(common, "setup_jax", lambda: None)   # no compile cache here
    monkeypatch.setattr(common, "require_tpu", lambda n: jax.devices()[:n])
    monkeypatch.setattr(common, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    return harness.execute(workload, seed, 0.5, False, time.perf_counter())["result"]
