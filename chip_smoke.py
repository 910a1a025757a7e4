#!/usr/bin/env python3
"""Chip smoke test: the live FL round at the paper's TIL widths on a TPU.

With no arguments (one chip) it runs two phases through the normal entry
point, ``Experiment().transport(kind="thread").serve(clients, params0)``:

* ``dense`` — four VGG16 silos (``VGGConfig(image_size=224)``, the
  published widths: 134,268,738 parameters, a 537 MB fp32 update) train
  with AdamW, ship their updates over the loopback socket transport and
  are folded by the round engine, for two rounds.  Round 1 is checked
  against a plain reference: each client's ``train`` on the same initial
  weights, averaged by example count on the host in float64.  The same
  updates also go through the ``fedavg_reduce`` kernel.
* ``int8`` — one round with ``aggregation(compression="int8")``, so the
  fused ``dequant_fold`` kernel folds the quantized deltas.  The fold is
  checked against the jitted jnp fold on the same payloads.

Every round must fold 4 of 4 silos with no revocation, deadline miss or
straggler escalation on the bus (a device error inside a silo would
surface there: the worker turns any exception into a crash), and every
evaluation loss must be finite.  Each phase prints compile seconds, round
wall times, ``c_msg_train`` bytes, peak device memory, the reference
difference and whether each kernel was lowered as a Mosaic custom call.

``--chips 4`` runs only the cross-chip path, the sharded parent fold of
``Experiment.hierarchy(sharded=True)``: four TIL-size regional
accumulators psum-folded over a 4-chip ``("pod",)`` mesh, against the
same four folded in sequence on one chip.

The compile cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, else in ``.jax_cache`` beside this file.  There is no CPU fallback:
without a TPU the script exits non-zero.  The last line of standard
output is ``{"ok": true, "device": {...}}``; any failed check raises.

Usage:
  python chip_smoke.py              # one chip: dense + int8 phases
  python chip_smoke.py --chips 4    # four chips: sharded hierarchy fold
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

SEED = 0
N_SILOS = 4
SAMPLES = (32, 16)          # (train, test) examples per silo
BATCH = 16
LEARNING_RATE = 1e-3
REPLY_TIMEOUT_S = 600.0
DENSE_ROUNDS = 2
INT8_ROUNDS = 1
# Agreement bound between two fp32 folds of the same updates that sum in
# a different order (or against a float64 sum): 16 fp32 ulps of the
# largest magnitude involved.
ULPS = 16


def tolerance(values: Any) -> float:
    import numpy as np

    scale = max(1.0, float(np.max(np.abs(values))))
    return ULPS * float(np.finfo(np.float32).eps) * scale


def vgg_config() -> Any:
    from repro.models.fl_models import VGGConfig

    return VGGConfig(image_size=224)


def setup_compile_cache() -> None:
    """Point JAX's persistent compile cache at a fixed directory.  Must
    run before the first JAX computation."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache")
        )


def require_tpu(n_chips: int) -> List[Any]:
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        raise SystemExit(f"chip_smoke: no TPU found ({exc})")
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX platform is {platform!r})"
        )
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: needs {n_chips} chips, JAX sees {len(devices)}"
        )
    return devices


class CompileClock:
    """Sums backend compile time (compile or persistent-cache load)
    and counts persistent-cache hits while a phase runs."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {
            "s": self.seconds,
            "hits": self.cache_hits,
            "misses": self.cache_misses,
        }

    def since(self, start: Dict[str, float]) -> Dict[str, float]:
        now = self.snapshot()
        return {
            "compile_s": round(now["s"] - start["s"], 3),
            "cache_hits": int(now["hits"] - start["hits"]),
            "cache_misses": int(now["misses"] - start["misses"]),
        }


def peak_bytes(device: Any) -> Optional[int]:
    stats = device.memory_stats()
    return None if stats is None else int(stats.get("peak_bytes_in_use", 0))


def lowered_as_mosaic(fn: Callable[..., Any], *shapes: Any) -> bool:
    """Compile ``fn`` for these argument shapes and report whether the
    program holds a Pallas kernel (a Mosaic ``tpu_custom_call``)."""
    text = fn.lower(*shapes, interpret=False).compile().as_text()
    return "tpu_custom_call" in text


def build_clients(silos: Sequence[Any], compression: Optional[str]) -> List[Any]:
    import jax.numpy as jnp

    from repro.federated import FLClient
    from repro.models.fl_models import softmax_cross_entropy, vgg16_forward
    from repro.optim import make_optimizer

    cfg = vgg_config()

    def loss_fn(p: Any, batch: Any) -> Any:
        x, y = batch
        return softmax_cross_entropy(vgg16_forward(p, x, cfg), y)

    def eval_fn(p: Any, batch: Any) -> Dict[str, Any]:
        x, y = batch
        logits = vgg16_forward(p, x, cfg)
        return {
            "loss_sum": softmax_cross_entropy(logits, y) * x.shape[0],
            "n_correct": jnp.sum(jnp.argmax(logits, axis=-1) == y),
        }

    opt = make_optimizer("adamw", LEARNING_RATE)
    return [
        FLClient(
            s.client_id, s, loss_fn, opt, batch_size=BATCH,
            local_epochs=1, eval_fn=eval_fn, compression=compression,
        )
        for s in silos
    ]


def check_rounds(driver: Any, result: Any, n_rounds: int) -> List[Dict[str, Any]]:
    """The live-round contract: every silo folded in every round, no
    recovery path taken, finite losses."""
    from repro.core.events import (
        DeadlineExpired,
        RevocationOccurred,
        StragglerEscalated,
    )

    faults = [
        ev for ev in driver.trace
        if isinstance(ev, (RevocationOccurred, DeadlineExpired, StragglerEscalated))
    ]
    if faults:
        raise AssertionError(f"recovery path taken: {faults}")
    if len(result.rounds) != n_rounds:
        raise AssertionError(f"{len(result.rounds)} of {n_rounds} rounds ran")
    rows = []
    for rec in result.rounds:
        if len(rec.fold_times_s) != N_SILOS:
            raise AssertionError(
                f"round {rec.round_idx} folded {len(rec.fold_times_s)} of "
                f"{N_SILOS} silos"
            )
        if rec.carried_over or rec.carried_in:
            raise AssertionError(
                f"round {rec.round_idx} carried over {rec.carried_over} / "
                f"in {rec.carried_in}"
            )
        loss = rec.metrics.get("loss", float("nan"))
        if not math.isfinite(loss):
            raise AssertionError(f"round {rec.round_idx} loss is {loss}")
        rows.append({
            "round": rec.round_idx,
            "folded": len(rec.fold_times_s),
            "loss": loss,
            "wall_s": round(
                rec.train_time_s + rec.eval_time_s + rec.checkpoint_time_s, 3
            ),
            "train_and_fold_s": round(rec.train_time_s, 3),
            "fold_s": round(rec.agg_time_s, 3),
            "c_msg_train_bytes": rec.message_log.c_msg_train_bytes,
            "s_msg_train_bytes": rec.message_log.s_msg_train_bytes,
        })
    return rows


def report_live(phase: str, rows: List[Dict[str, Any]], peak: Optional[int],
                compile_stats: Dict[str, float]) -> None:
    """Print the live rounds as soon as they pass, before the reference
    checks, so a later failure still leaves them on record."""
    print("live " + json.dumps({
        "phase": phase, "rounds": rows, "peak_bytes_in_use": peak,
        **compile_stats,
    }), flush=True)


def host_flat(plan: Any, tree: Any) -> Any:
    import numpy as np

    return np.asarray(plan.flatten(tree))


def phase_dense(device: Any, clock: CompileClock) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Experiment
    from repro.data import make_classification_silos
    from repro.federated import AggregationEngine
    from repro.federated.agg_engine import plan_for
    from repro.kernels.fedavg_reduce import fedavg_reduce
    from repro.models.fl_models import init_vgg16

    start = clock.snapshot()
    cfg = vgg_config()
    image = (cfg.image_size, cfg.image_size, 3)
    silos = make_classification_silos(
        N_SILOS, cfg.n_classes, image, [SAMPLES] * N_SILOS, seed=SEED
    )
    clients = build_clients(silos, compression=None)
    params0 = init_vgg16(jax.random.PRNGKey(SEED), cfg)
    plan = plan_for(params0)

    driver = (Experiment()
              .transport(kind="thread", reply_timeout_s=REPLY_TIMEOUT_S)
              .serve(clients, params0))
    with driver:
        result = driver.run(DENSE_ROUNDS)
    rows = check_rounds(driver, result, DENSE_ROUNDS)
    peak_live = peak_bytes(device)
    report_live("dense", rows, peak_live, clock.since(start))
    live = host_flat(plan, driver.fold_reports[0].params)
    del driver, result

    # Plain reference: the same clients trained on the same weights,
    # averaged by example count in float64 on the host.
    acc = np.zeros(plan.total_elems, np.float64)
    weights = []
    updates = []
    for c in clients:
        r = c.train(params0)
        updates.append(r.params)
        acc += r.n_samples * host_flat(plan, r.params).astype(np.float64)
        weights.append(float(r.n_samples))
    ref = acc / sum(weights)
    tol = tolerance(ref)
    diff = float(np.max(np.abs(live.astype(np.float64) - ref)))
    if not diff <= tol:
        raise AssertionError(f"dense fold differs from reference by {diff} > {tol}")

    # The same updates through the flatten-once fedavg_reduce path.
    engine = AggregationEngine()
    kernel_avg = host_flat(plan, engine.aggregate(updates, weights))
    kernel_diff = float(np.max(np.abs(kernel_avg.astype(np.float64) - ref)))
    if not kernel_diff <= tol:
        raise AssertionError(
            f"fedavg_reduce differs from reference by {kernel_diff} > {tol}"
        )
    del updates
    mosaic = lowered_as_mosaic(
        fedavg_reduce,
        jax.ShapeDtypeStruct((N_SILOS, plan.total_elems), jnp.float32),
        jax.ShapeDtypeStruct((N_SILOS,), jnp.float32),
    )
    if not (engine.use_pallas and mosaic):
        raise AssertionError("fedavg_reduce did not run as a Mosaic kernel")
    return {
        "phase": "dense",
        "params": plan.total_elems,
        "rounds": rows,
        **clock.since(start),
        "peak_bytes_live_rounds": peak_live,
        "peak_bytes_in_use": peak_bytes(device),
        "ref_max_abs_diff": diff,
        "fedavg_reduce_max_abs_diff": kernel_diff,
        "tolerance": tol,
        "fedavg_reduce_tpu_custom_call": mosaic,
    }


def phase_int8(device: Any, clock: CompileClock) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Experiment
    from repro.core.events import UpdateFolded
    from repro.data import make_classification_silos
    from repro.federated.agg_engine import (
        _flat_dequant_fold_jnp,
        _flat_finalize,
        plan_for,
    )
    from repro.federated.compression import QBLOCK, ClientCompressor
    from repro.kernels.fedavg_reduce import dequant_fold
    from repro.models.fl_models import init_vgg16

    class RecordingCompressor(ClientCompressor):
        """The client's int8 encoder, keeping what it put on the wire."""

        def __init__(self, spec: Any) -> None:
            super().__init__(spec)
            self.sent: List[Any] = []

        def encode(self, global_params: Any, local_params: Any,
                   base_round: Optional[int] = None) -> Any:
            update = super().encode(global_params, local_params, base_round)
            self.sent.append(update)
            return update

    start = clock.snapshot()
    cfg = vgg_config()
    image = (cfg.image_size, cfg.image_size, 3)
    silos = make_classification_silos(
        N_SILOS, cfg.n_classes, image, [SAMPLES] * N_SILOS, seed=SEED
    )
    clients = build_clients(silos, compression="int8")
    for c in clients:
        c.compressor = RecordingCompressor(c.compressor.spec)
    params0 = init_vgg16(jax.random.PRNGKey(SEED), cfg)
    plan = plan_for(params0)

    driver = (Experiment()
              .transport(kind="thread", reply_timeout_s=REPLY_TIMEOUT_S)
              .aggregation(compression="int8")
              .serve(clients, params0))
    with driver:
        result = driver.run(INT8_ROUNDS)
    rows = check_rounds(driver, result, INT8_ROUNDS)
    peak_live = peak_bytes(device)
    report_live("int8", rows, peak_live, clock.since(start))
    folds = [
        ev for ev in driver.trace
        if isinstance(ev, UpdateFolded) and ev.round_idx == 1
    ]
    live = host_flat(plan, driver.fold_reports[0].params)
    del driver, result

    # The same round-1 payloads, in the same fold order, through the
    # jitted jnp dequantize-and-fold.
    sent = {str(c.client_id): c.compressor.sent[0] for c in clients}
    padded = -(-plan.total_elems // QBLOCK) * QBLOCK
    nb = padded // QBLOCK

    def device_payload(update: Any) -> Any:
        data = np.zeros(padded, np.int8)
        data[: update.total_elems] = update.data
        return jnp.asarray(data), jnp.asarray(update.scales, jnp.float32)

    acc = jnp.zeros(padded, jnp.float32)
    wsum = 0.0
    for ev in folds:
        data, scales = device_payload(sent[ev.task])
        acc = _flat_dequant_fold_jnp(acc, data, scales, jnp.float32(ev.folded_weight))
        wsum += ev.folded_weight
    ref = np.asarray(
        _flat_finalize(acc, plan.flatten(params0), jnp.float32(1.0 / wsum))
    )
    tol = tolerance(ref)
    diff = float(np.max(np.abs(live - ref)))
    if not diff <= tol:
        raise AssertionError(f"int8 fold differs from the jnp fold by {diff} > {tol}")

    # One payload through both folds directly, from the same accumulator.
    data, scales = device_payload(sent[folds[0].task])
    base = jax.random.normal(jax.random.PRNGKey(SEED + 1), (padded,), jnp.float32)
    w = jnp.float32(folds[0].folded_weight)
    kernel_out = np.asarray(dequant_fold(jnp.array(base), data, scales, w))
    jnp_out = np.asarray(_flat_dequant_fold_jnp(jnp.array(base), data, scales, w))
    kernel_diff = float(np.max(np.abs(kernel_out - jnp_out)))
    if not kernel_diff <= tolerance(jnp_out):
        raise AssertionError(f"dequant_fold differs from the jnp fold by {kernel_diff}")
    mosaic = lowered_as_mosaic(
        dequant_fold,
        jax.ShapeDtypeStruct((padded,), jnp.float32),
        jax.ShapeDtypeStruct((padded,), jnp.int8),
        jax.ShapeDtypeStruct((nb,), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.float32),
    )
    if not mosaic:
        raise AssertionError("dequant_fold did not lower as a Mosaic kernel")
    return {
        "phase": "int8",
        "params": plan.total_elems,
        "rounds": rows,
        **clock.since(start),
        "peak_bytes_live_rounds": peak_live,
        "peak_bytes_in_use": peak_bytes(device),
        "ref_max_abs_diff": diff,
        "dequant_fold_vs_jnp_max_abs_diff": kernel_diff,
        "tolerance": tol,
        "dequant_fold_tpu_custom_call": mosaic,
    }


def phase_sharded(devices: Sequence[Any], clock: CompileClock) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.federated.agg_engine import PartialSum, plan_for
    from repro.federated.hierarchy import HierarchyCoordinator, ShardedPartialFolder
    from repro.kernels.fedavg_reduce import BLOCK
    from repro.models.fl_models import init_vgg16

    start = clock.snapshot()
    cfg = vgg_config()
    base = init_vgg16(jax.random.PRNGKey(SEED), cfg)
    plan = plan_for(base)
    padded = -(-plan.total_elems // BLOCK) * BLOCK
    regions = {f"region_{r}": [f"client_{r}"] for r in range(len(devices))}

    def accumulator(key: Any) -> Any:
        # A regional accumulator: weighted deltas, zero past the model.
        acc = 1e-3 * jax.random.normal(key, (padded,), jnp.float32)
        return jnp.where(jnp.arange(padded) < plan.total_elems, acc, 0.0)

    keys = jax.random.split(jax.random.PRNGKey(SEED + 2), len(regions))
    partials = [
        PartialSum(
            acc=accumulator(k), wsum=float(32 * (i + 1)), n_clients=1,
            plan_signature=plan.signature, base_round=1, region_id=rid,
        )
        for i, (rid, k) in enumerate(zip(regions, keys))
    ]
    jax.block_until_ready([p.acc for p in partials])

    def fold(coordinator: Any) -> Any:
        coordinator.fold_partials(1, partials, base)  # compile + warm up
        t = time.perf_counter()
        out = coordinator.fold_partials(1, partials, base)
        jax.block_until_ready(out)
        return out, time.perf_counter() - t

    sequential, seq_s = fold(HierarchyCoordinator(regions))
    sharded, shard_s = fold(HierarchyCoordinator(regions, sharded=True))
    a = host_flat(plan, sequential)
    b = host_flat(plan, sharded)
    diff = float(np.max(np.abs(a - b)))
    tol = tolerance(a)
    if not diff <= tol:
        raise AssertionError(f"sharded fold differs from sequential by {diff} > {tol}")

    folder = ShardedPartialFolder()
    stack = folder.place([p.acc for p in partials])
    rows = {str(s.device.id): list(s.data.shape) for s in stack.addressable_shards}
    if len(rows) != len(devices) or any(r[0] != 1 for r in rows.values()):
        raise AssertionError(f"expected one row per chip, got {rows}")
    text = folder.reduce_fn().lower(stack).compile().as_text()
    if "all-reduce" not in text:
        raise AssertionError("the sharded fold has no all-reduce")
    return {
        "phase": "sharded_fold",
        "accumulator_bytes": padded * 4,
        "rows_per_device": rows,
        "all_reduce": True,
        "max_abs_diff": diff,
        "bitwise_equal": bool(np.array_equal(a, b)),
        "tolerance": tol,
        "sequential_fold_s": round(seq_s, 4),
        "sharded_fold_s": round(shard_s, 4),
        **clock.since(start),
        "peak_bytes_in_use": [peak_bytes(d) for d in devices],
    }


def main(argv: Optional[Sequence[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: live dense + int8 rounds; 4: the sharded hierarchy fold",
    )
    args = parser.parse_args(argv)
    setup_compile_cache()
    devices = require_tpu(args.chips)
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        phases = [phase_sharded(devices[:4], clock)]
    else:
        phases = [phase_dense(devices[0], clock), phase_int8(devices[0], clock)]
    for phase in phases:
        print("phase " + json.dumps(phase), flush=True)
    print(f"total_s {time.perf_counter() - t0:.1f}", flush=True)
    import jax

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
        },
    }))


if __name__ == "__main__":
    main()
