"""A configuration joins the benchmark with new files only: its sizes and
program parts under ``bench/configs/``, a cell under ``bench/cells/``, its
CPU test sizes under ``tests/bench/sizes/`` and entries in
``BENCHMARK.json``; no existing file of the benchmark changes."""
import hashlib
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from bench import calibrate, common  # noqa: E402

WATCHED = (common.BENCH, os.path.dirname(os.path.abspath(__file__)))


def _digest(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.relpath(os.path.join(d, f), root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def _write(path, data):
    path.write_text(json.dumps(data, indent=2))


def test_a_new_configuration_needs_only_new_files(tmp_path, monkeypatch):
    before = {root: _digest(root) for root in WATCHED}
    root = tmp_path / "bench"
    shutil.copytree(common.BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    sizes = tmp_path / "sizes"
    shutil.copytree(bench_tiny.SIZES, sizes)

    # The new files: a renamed copy of the Shakespeare configuration, a
    # cell, CPU sizes with two silos, and the entries in BENCHMARK.json.
    cfg = common.load_json(root / "configs" / "shakespeare_lstm.json")
    _write(root / "configs" / "probe_lstm.json", {**cfg, "name": "probe_lstm"})
    shutil.copy(root / "configs" / "shakespeare_lstm.py", root / "configs" / "probe_lstm.py")
    shutil.copy(root / "cells" / "shakespeare_dense.json", root / "cells" / "probe_dense.json")
    shakespeare = common.load_json(sizes / "shakespeare_lstm.json")
    _write(sizes / "probe_lstm.json", {
        "tiny": {"model": {"hidden": 32, "seq_len": 12},
                 "silos": {"train": [40, 50], "test": [10, 12]}},
        "control": shakespeare["control"]})
    bench = common.benchmark()
    bench["configs"].append({
        "name": "probe_lstm", "source": "https://arxiv.org/abs/2308.08967",
        "file": "bench/configs/probe_lstm.json", "reduced": [], "why": "a new configuration"})
    bench["workloads"].append({
        "name": "probe_dense", "config": "probe_lstm", "traffic": "dense", "chips": 1,
        "why": "a new cell"})
    _write(tmp_path / "BENCHMARK.json", bench)

    monkeypatch.setattr(common, "BENCH", str(root))
    monkeypatch.setattr(common, "REPO", str(tmp_path))
    monkeypatch.setattr(bench_tiny, "SIZES", str(sizes))

    # A cell reports every metric that lists no cells of its own.
    bench = common.benchmark()
    e2e = [m["name"] for m in common.metrics_for(bench, "probe_dense", False)]
    assert e2e == [m["name"] for m in bench["end_to_end"] if "workloads" not in m]
    layer = [m["name"] for m in common.metrics_for(bench, "probe_dense", True)]
    assert layer == [m["name"] for m in bench["per_layer"] if "workloads" not in m]

    out = bench_tiny.run(monkeypatch, "probe_dense")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 2
    assert sorted(out["metrics"]) == sorted(e2e)

    control, module = bench_tiny.config("probe_lstm", bench_tiny.CONTROL)
    limits = common.cell("probe_dense")["limits"]
    got = calibrate.readings(control, module, None, 2**31 + 23, ["control"])
    assert any(got["control"][k] > v for k, v in limits.items()), got["control"]

    assert {r: _digest(r) for r in WATCHED} == before
