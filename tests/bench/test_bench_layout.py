"""BENCHMARK.json against the benchmark's files, the window rule, and the
run without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from bench import common, harness  # noqa: E402

BENCH = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_and_metric_resolves_to_its_files():
    for cfg in BENCH["configs"]:
        data, module = common.config(cfg["name"])
        assert os.path.join(REPO, cfg["file"]) == os.path.join(
            common.BENCH, "configs", cfg["name"] + ".json")
        for fn in ("init_params", "make_silos", "program_parts", "ref_loss", "forward_flops"):
            assert callable(getattr(module, fn)), (cfg["name"], fn)
        assert set(cfg["reduced"]) <= set(data), cfg["name"]
    for w in BENCH["workloads"]:
        common.config(w["config"])
        common.traffic(w["traffic"])
        assert common.cell(w["name"])["limits"], w["name"]
        assert common.metrics_for(BENCH, w["name"], False), w["name"]
        assert common.metrics_for(BENCH, w["name"], True), w["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(common.metric(m["name"]).read), m["name"]


def test_names_units_and_bounds_keep_to_the_contract():
    names = [x["name"] for x in BENCH["configs"] + BENCH["workloads"]
             + BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(x["name"] for x in BENCH["end_to_end"] + BENCH["per_layer"])) == \
        len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    n = len(BENCH["workloads"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, n // 2)
    # A full check with 24 cells fits its 12 hours.
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_a_new_metric_traffic_or_cell_file_needs_no_edit(tmp_path, monkeypatch):
    root = tmp_path / "bench"
    shutil.copytree(common.BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    (root / "metrics" / "rounds_n.py").write_text(
        "def read(run):\n    return float(len(run.rounds))\n")
    (root / "traffic" / "fp16.json").write_text(json.dumps({"compression": "fp16"}))
    (root / "cells" / "til_fp16.json").write_text(json.dumps({"limits": {"change_gap": 0.1}}))
    before = {p: open(os.path.join(common.BENCH, p)).read()
              for p in ("common.py", "harness.py")}
    monkeypatch.setattr(common, "BENCH", str(root))
    assert common.metric("rounds_n").read(type("R", (), {"rounds": [1, 2]})()) == 2.0
    assert common.traffic("fp16")["compression"] == "fp16"
    assert common.cell("til_fp16")["limits"] == {"change_gap": 0.1}
    after = {p: open(os.path.join(common.BENCH, p)).read() for p in before}
    assert before == after


def _within(over, base):
    """The override's keys that ``base`` does not have, nested as a path."""
    out = []
    for k, v in over.items():
        if k not in base:
            out.append(k)
        elif isinstance(v, dict):
            out += [f"{k}.{x}" for x in _within(v, base[k])]
    return out


def test_every_configuration_has_cpu_sizes_that_only_cut():
    for c in BENCH["configs"]:
        data, _ = common.config(c["name"])
        sizes = bench_tiny.sizes_of(c["name"])
        for kind in (bench_tiny.TINY, bench_tiny.CONTROL):
            assert isinstance(sizes.get(kind), dict), (c["name"], kind)
            assert not _within(sizes[kind], data), (c["name"], kind, _within(sizes[kind], data))


def test_a_configuration_without_cpu_sizes_names_the_missing_file(tmp_path, monkeypatch):
    monkeypatch.setattr(bench_tiny, "SIZES", str(tmp_path))
    with pytest.raises(FileNotFoundError) as err:
        bench_tiny.config("shakespeare_lstm")
    assert str(tmp_path / "shakespeare_lstm.json") in str(err.value)
    assert '"tiny"' in str(err.value) and '"control"' in str(err.value)


@pytest.mark.parametrize("name, kind, model, silos", [
    ("til_vgg16", "tiny", {"image_size": 16, "stages": [[8, 1], [16, 1]], "fc_width": 32},
     {"train": [48, 48, 40, 48], "test": [20, 20, 20, 17], "dirichlet_alpha": 0.5}),
    ("til_vgg16", "control", {"image_size": 32},
     {"train": [160, 160], "test": [16, 16], "dirichlet_alpha": 0.5}),
    ("shakespeare_lstm", "tiny", {"hidden": 32, "seq_len": 12},
     {"scale": 0.035, "train": [40, 50, 35], "test": [10, 12, 7]}),
    ("shakespeare_lstm", "control", {"hidden": 32, "seq_len": 12},
     {"scale": 0.035, "train": [40, 50, 35], "test": [10, 12, 7]}),
])
def test_cpu_sizes_merge_into_the_configuration(name, kind, model, silos):
    full, _ = common.config(name)
    cfg, _ = bench_tiny.config(name, kind)
    assert cfg["model"] == {**full["model"], **model}
    assert cfg["silos"] == silos
    assert {k: v for k, v in cfg.items() if k not in ("model", "silos")} == \
        {k: v for k, v in full.items() if k not in ("model", "silos")}
    assert common.config(name)[0] == full      # the configuration's own dict is untouched


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _rounds(clock, lengths):
    it = iter(lengths)

    def one_round(i):
        start = clock.t
        clock.t += next(it)
        return type("Obs", (), {"start": start, "end": clock.t, "index": i})()
    return one_round


@pytest.mark.parametrize("lengths, seconds, expect", [
    ([2.0] * 100, 51.0, 25),     # 25 whole rounds: 50 + 2 > 51 stops
    ([55.0, 55.0], 51.0, 1),     # a round longer than the window: one round
    ([10.0, 30.0, 5.0], 51.0, 2),  # 40 + 30 > 51 after the second
    ([1.0] * 10, 5.0, 5),        # 4 + 1 <= 5 allows a 5th; 5 + 1 > 5 stops
])
def test_window_holds_whole_rounds(lengths, seconds, expect):
    clock = _Clock()
    seen = []
    rounds, window = harness.drive_window(
        _rounds(clock, lengths), seconds, lambda r, e: seen.append(e), clock=clock)
    assert len(rounds) == expect
    assert [r.index for r in rounds] == list(range(1, expect + 1))
    assert window == pytest.approx(sum(lengths[:expect]))
    assert seen == pytest.approx([sum(lengths[:k]) for k in range(1, expect + 1)])
    assert len(rounds) == 1 or window <= seconds


def _run_py(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload", "til_dense",
         "--seed", "2147483649", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    proc = _run_py(REPO, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert _no_result(proc.stdout)


def test_a_checkout_of_only_the_benchmark_fails_and_prints_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_result(proc.stdout)
