"""Tiny sizes of the benchmark's configurations, for CPU tests.

Widths are cut here only; the cells run the published ones.  Each
configuration's sizes are a data file of its own,
``tests/bench/sizes/<config>.json``: ``{"tiny": {...}, "control": {...}}``,
overrides deep-merged into ``bench/configs/<config>.json``, so a new
configuration needs no edit here."""
import copy
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402

from bench import common, harness  # noqa: E402

SIZES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "sizes")
TINY = "tiny"
CONTROL = "control"     # sizes at which the bf16 control separates from float32

_CONFIG = common.config


def merge(base, over):
    """``base`` with ``over`` laid on it: a dict merges key by key, any
    other value (lists included) replaces."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(base[k], v) if isinstance(v, dict) and isinstance(base.get(k), dict) else v
    return out


def sizes_of(name):
    path = os.path.join(SIZES, f"{name}.json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no CPU test sizes for configuration {name!r}: add {path} holding "
            '{"tiny": {...}, "control": {...}}, overrides of keys of its '
            "bench/configs JSON that cut it to CPU size (tiny) and to the size at "
            "which the bf16 control fails its cell's limits (control)")
    return common.load_json(path)


def config(name, sizes=TINY):
    cfg, module = _CONFIG(name)
    return merge(copy.deepcopy(cfg), sizes_of(name)[sizes]), module


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
