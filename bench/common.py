"""Lookup by name, and the JAX set-up every run shares.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` (sizes) and ``<config>.py`` (weights,
  data, the program's loss and optimizer, the plain reference, FLOPs);
* ``bench/traffic/<traffic>.json`` (the round protocol: codec);
* ``bench/cells/<workload>.json`` (the limits of the ``correct`` check);
* ``bench/metrics/<metric>.py`` (a ``read(run)`` that returns the value,
  or None where the run holds nothing to read).

Adding any of them needs no edit to an existing file.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(REPO, ".jax_cache")


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(REPO, "BENCHMARK.json"))


def _module(path: str, name: str) -> ModuleType:
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    assert spec is not None and spec.loader is not None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name: str) -> Tuple[Dict[str, Any], ModuleType]:
    cfg = load_json(os.path.join(BENCH, "configs", f"{name}.json"))
    return cfg, _module(os.path.join(BENCH, "configs", f"{name}.py"), f"bench_config_{name}")


def traffic(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH, "traffic", f"{name}.json"))


def cell(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH, "cells", f"{name}.json"))


def metric(name: str) -> ModuleType:
    return _module(os.path.join(BENCH, "metrics", f"{name}.py"),
                   "bench_metric_" + name.replace(".", "_"))


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: Dict[str, Any], name: str, trace: bool) -> List[Dict[str, Any]]:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer ones."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if name in m.get("workloads", [name])]


def peaks(device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; a kind not in the table is an error."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def seed_key(seed: int) -> Any:
    """A PRNG key from a seed of any size (JAX keys take 32 bits)."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def setup_jax() -> None:
    """Compile cache in ``$JAX_COMPILATION_CACHE_DIR`` if set, else at a
    fixed path in the checkout; every program is cached.  Call before the
    first JAX computation."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_tpu(n_chips: int) -> List[Any]:
    """The run's devices; exits without a result where JAX finds no TPU
    or fewer chips than the cell asks for."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise SystemExit(f"bench: no TPU found ({exc})")
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform is {devices[0].platform!r})")
    if len(devices) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} chips, JAX sees {len(devices)}")
    return devices[:n_chips]


class CompileClock:
    """Counts XLA compilations and persistent-cache loads."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        return self.compiles + self.cache_hits


def peak_bytes(device: Any) -> Optional[int]:
    stats = device.memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))
