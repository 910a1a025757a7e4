"""One benchmark run of one cell: set-up, the measured window of live FL
rounds, the trace reduction, and the check against the plain reference.

The window drives the program's normal path and nothing else:
``Experiment().transport(kind="thread")[.aggregation(compression=...)]
.serve(clients, params0)``, a ``LiveRoundDriver`` whose silos are
``FLClient`` workers behind the loopback socket transport.  Rounds run
back to back (a closed loop); a new round starts only while the window's
elapsed time plus the last round's time stays within ``--seconds``, and at
least one round always runs.

The benchmark records, from its own files only:

* the host clock at each round's start and end (``driver.run(1)``);
* each silo's ``train`` and ``evaluate`` span (a thin ``FLClient``
  subclass that calls the program's own methods);
* when each ``c_msg_train`` reaches the round driver (a wrapper around the
  transport's ``poll``);
* with ``--trace 1``, a ``TraceAnnotation`` at each of those points, on
  the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from bench import common

REPLY_TIMEOUT_S = 240.0     # a silo silent this long fails its update
TRACE_SECONDS = 3.0         # trace whole rounds until this much is traced


class HeldSilo:
    """A silo whose data was made once, in set-up, and is held on the
    host; ``batches`` slices it in order, so the window times no RNG."""

    def __init__(self, client_id: str, train: Tuple[Any, Any], test: Tuple[Any, Any]) -> None:
        self.client_id = client_id
        self.train = train
        self.test = test

    def batches(self, batch: int, split: str = "train"):
        x, y = self.train if split == "train" else self.test
        for i in range(0, len(y), batch):
            yield x[i:i + batch], y[i:i + batch]

    def one_of_each_shape(self, batch: int) -> "HeldSilo":
        """The first batch and, where it is short, the last batch of each
        split: every shape the silo's rounds will feed its programs."""
        def pick(data):
            x, y = data
            idx = list(range(min(batch, len(y))))
            tail = len(y) % batch
            if tail and len(y) > batch:
                idx += list(range(len(y) - tail, len(y)))
            return x[idx], y[idx]
        return HeldSilo(self.client_id, pick(self.train), pick(self.test))


@dataclasses.dataclass
class Span:
    kind: str
    client_id: str
    round_no: int
    start: float
    end: float
    reported_s: float


@dataclasses.dataclass
class RoundObs:
    """One round of the window, as the benchmark saw it."""

    index: int
    start: float
    end: float
    record: Any                                   # the program's RoundRecord
    receipts: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def wall_s(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Run:
    """What a metric reader sees of a finished run."""

    cfg: Dict[str, Any]
    module: Any
    n_chips: int
    peaks: Dict[str, Any]
    setup_s: float
    window_s: float
    rounds: List[RoundObs]
    spans: List[Span]
    n_params: int
    trace: Optional[Dict[str, Any]] = None        # see ``_reduce_trace``

    def silo_train_spans(self) -> List[Span]:
        return [s for s in self.spans if s.kind == "train"]


class FirstSteps:
    """What the program's own compiled step did on a silo's first
    ``FIRST_STEPS`` calls of the window's first round: each loss, the
    first gradient's leaf norms as the optimizer state holds it after
    one step, and the leaf norms of the weights' change as the next call
    gets them (or as the round returns them).  Device values, reduced on
    the device by programs compiled in set-up; read after the window."""

    def __init__(self, first_grad: Callable[[Any], Tuple[Any, float]]) -> None:
        self.first_grad = first_grad
        self.base: Any = None
        self.losses: List[Any] = []
        self.grad: Any = None
        self.grad_scale = 1.0
        self.change: Any = None

    def before(self, params: Any) -> None:
        from bench import fl_reference as flr

        if not self.losses:
            self.base = params
        elif len(self.losses) == flr.FIRST_STEPS and self.change is None:
            self.close(params)

    def after(self, opt_state: Any, loss: Any) -> None:
        from bench import fl_reference as flr

        if len(self.losses) < flr.FIRST_STEPS:
            self.losses.append(loss)
            if len(self.losses) == 1:
                tree, self.grad_scale = self.first_grad(opt_state)
                self.grad = flr.leaf_norms(tree)

    def close(self, params: Any) -> None:
        from bench import fl_reference as flr

        if self.change is None and self.base is not None:
            self.change = flr.change_norms(params, self.base)
        self.base = None

    def read(self) -> Dict[str, Any]:
        return {"loss": [float(l) for l in self.losses],
                "grad": [float(v) * self.grad_scale for v in self.grad],
                "change": [float(v) for v in self.change]}


def _timed_client_class(first_grad: Callable[[Any], Tuple[Any, float]]):
    import jax

    from repro.federated import FLClient

    class TimedClient(FLClient):
        """``FLClient`` whose ``train`` and ``evaluate`` are the program's
        own, with the benchmark's host-clock span around each call; in
        the window's first round its compiled step is watched by a
        ``FirstSteps``."""

        spans: List[Span] = []
        round_no = [0]
        first_steps: Dict[str, FirstSteps] = {}

        def __init__(self, *args: Any, **kwargs: Any) -> None:
            super().__init__(*args, **kwargs)
            self._watch: Optional[FirstSteps] = None
            step = self._train_step

            def watched_step(params, opt_state, batch):
                watch = self._watch
                if watch is not None:
                    watch.before(params)
                params, opt_state, loss = step(params, opt_state, batch)
                if watch is not None:
                    watch.after(opt_state, loss)
                return params, opt_state, loss
            self._train_step = watched_step

        def train(self, global_params):
            if self.round_no[0] <= 1 and self.client_id not in self.first_steps:
                self._watch = self.first_steps[self.client_id] = FirstSteps(first_grad)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_silo_train"):
                result = super().train(global_params)
            if self._watch is not None:
                self._watch.close(result.params)
                self._watch = None
            self.spans.append(Span("train", self.client_id, self.round_no[0], t0,
                                   time.perf_counter(), result.train_time_s))
            return result

        def evaluate(self, aggregated_params):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench_silo_eval"):
                result = super().evaluate(aggregated_params)
            self.spans.append(Span("eval", self.client_id, self.round_no[0], t0,
                                   time.perf_counter(), result.eval_time_s))
            return result

    TimedClient.spans = []
    TimedClient.first_steps = {}
    return TimedClient


def _warm_up(clients: List[Any], silos: List[HeldSilo], params0: Any,
             codec: Optional[str], batch: int) -> None:
    """Run every program the window runs once, on every shape it will
    see, with arguments of the kinds the round passes (weights that came
    off the wire are ``jnp.asarray`` of host arrays).  The clients'
    ``FirstSteps`` watch these calls too, which compiles their
    reductions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.federated import AggregationEngine
    from repro.federated.compression import (
        ClientCompressor, deserialize_update, parse_compression, serialize_update,
    )

    off_wire = lambda tree: jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), tree)
    received = off_wire(params0)
    trained = None
    for client, silo in zip(clients, silos):
        client.silo = silo.one_of_each_shape(batch)
        try:
            trained = client.train(received).params
            client.evaluate(received)
        finally:
            client.silo = silo
    engine = AggregationEngine()
    if codec is None:
        agg = engine.streaming()
        for w in (1.0, 2.0):
            agg.add(off_wire(trained), w, block=True)
        jax.block_until_ready(agg.result())
        return
    update = ClientCompressor(parse_compression(codec)).encode(received, trained)
    update = deserialize_update(serialize_update(update))
    base = params0
    for _ in range(2):      # round 1 folds against params0, later rounds
        agg = engine.streaming(base=base, base_round=1)  # against a fold's output
        agg.add(update, 1.0, block=True)
        base = agg.result()
        jax.block_until_ready(base)


def _reduce_trace(trace_dir: str, run: Run, n_traced: int) -> Dict[str, Any]:
    from bench import trace as tr

    data = tr.load(trace_dir, run.n_chips)
    rounds = [e for e in data["host"] if e[0] == "bench_round"][:n_traced]
    if not rounds:
        raise RuntimeError("the trace holds no bench_round span")
    window = (rounds[0][1], rounds[-1][1] + rounds[-1][2])
    # Host perf_counter -> trace clock, from each round's own start.
    phases: List[Tuple[str, float, float]] = []
    for obs, (_, start_ns, _) in zip(run.rounds, rounds):
        to_ns = lambda t, obs=obs, start_ns=start_ns: start_ns + (t - obs.start) * 1e9
        trains = [s for s in run.spans if s.kind == "train" and s.round_no == obs.index]
        rec = obs.record
        fold_end = obs.start + rec.train_time_s
        fold_start = fold_end - rec.agg_time_s
        first = min((s.start for s in trains), default=obs.start)
        last = max((s.end for s in trains), default=obs.start)
        for name, a, b in (("dispatch", obs.start, first),
                           ("silo compute", first, last),
                           ("receive", last, fold_start),
                           ("fold", fold_start, fold_end),
                           ("eval", fold_end, obs.end)):
            if b > a:
                phases.append((name, to_ns(a), to_ns(b)))
    devices = sorted(data["ops"])
    busy = [tr.busy_ns(data["ops"][d], window) for d in devices]
    idle = tr.gaps(data["ops"][devices[0]], window) if devices else []
    return {
        "window_ns": window,
        "window_s": (window[1] - window[0]) * 1e-9,
        "busy_s": (sum(busy) / len(busy)) * 1e-9 if busy else 0.0,
        "ops": data["ops"],
        "modules": data["modules"],
        "rounds": run.rounds[:n_traced],
        "breakdown": {
            "device_ops": tr.op_table(data["ops"][devices[0]], data["modules"].get(devices[0], []),
                                      window) if devices else [],
            "idle_gaps": tr.gap_table(idle, phases),
        },
    }


def drive_window(one_round: Callable[[int], Any], seconds: float,
                 after_round: Optional[Callable[[List[Any], float], None]] = None,
                 clock: Callable[[], float] = time.perf_counter) -> Tuple[List[Any], float]:
    """Whole rounds back to back.  A new round starts only while the
    elapsed time plus the last round's time stays within ``seconds``; the
    first always runs.  ``one_round(i)`` returns an object with ``start``
    and ``end`` on ``clock``.  Returns the rounds and the window's length."""
    rounds: List[Any] = []
    w0 = clock()
    while True:
        obs = one_round(len(rounds) + 1)
        rounds.append(obs)
        elapsed = obs.end - w0
        if after_round is not None:
            after_round(rounds, elapsed)
        if elapsed + (obs.end - obs.start) > seconds:
            return rounds, elapsed


def execute(workload_name: str, seed: int, seconds: float, trace: bool,
            t_start: float) -> Dict[str, Any]:
    """One run; returns the result line (as a dict) and the lines to
    print before it."""
    common.setup_jax()
    bench = common.benchmark()
    wl = common.workload(bench, workload_name)
    devices = common.require_tpu(wl["chips"])
    sys.path.insert(0, os.path.join(common.REPO, "src"))

    import jax

    from repro.core import Experiment
    from repro.core.events import DeadlineExpired, RevocationOccurred, StragglerEscalated
    from repro.federated.transport import MSG_C_TRAIN

    clock = common.CompileClock()
    cfg, module = common.config(wl["config"])
    traffic = common.traffic(wl["traffic"])
    limits = common.cell(workload_name)["limits"]
    kind = devices[0].device_kind
    peaks = common.peaks(kind)
    codec = traffic.get("compression")
    batch = cfg["batch_size"]

    # -- set-up -----------------------------------------------------------
    k_params, k_data = jax.random.split(common.seed_key(seed))
    params0 = jax.jit(lambda k: module.init_params(cfg, k))(k_params)
    jax.block_until_ready(params0)
    n_params = sum(int(l.size) for l in jax.tree.leaves(params0))
    data = module.make_silos(cfg, seed, k_data)
    silos = [HeldSilo(f"silo_{i}", d["train"], d["test"]) for i, d in enumerate(data)]
    parts = module.program_parts(cfg)
    client_cls = _timed_client_class(parts["first_grad"])
    clients = [
        client_cls(s.client_id, s, parts["loss_fn"], parts["optimizer"],
                   batch_size=batch, local_epochs=cfg["local_epochs"],
                   eval_fn=parts["eval_fn"], compression=codec)
        for s in silos
    ]
    _warm_up(clients, silos, params0, codec, batch)
    client_cls.spans.clear()
    client_cls.first_steps.clear()
    exp = Experiment().transport(kind="thread", reply_timeout_s=REPLY_TIMEOUT_S)
    if codec is not None:
        exp = exp.aggregation(compression=codec)
    driver = exp.serve(clients, params0)

    receipts: Dict[str, float] = {}
    poll = driver.transport.poll

    def stamped_poll(timeout):
        events = poll(timeout)
        now = time.perf_counter()
        for ev in events:
            if ev.kind == "message" and ev.header.get("kind") == MSG_C_TRAIN:
                receipts.setdefault(ev.client_id, now)
        return events

    driver.transport.poll = stamped_poll
    driver.start()
    setup_s = time.perf_counter() - t_start

    # -- window -----------------------------------------------------------
    compiles_before = clock.count()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    traced = [0]
    trace_timing: Dict[str, float] = {}

    def stop_trace(n_rounds: int) -> None:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        trace_timing["stop_s"] = time.perf_counter() - t0
        traced[0] = n_rounds

    def one_round(index: int) -> RoundObs:
        client_cls.round_no[0] = index
        receipts.clear()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench_round"):
            result = driver.run(1)
        return RoundObs(index, t0, time.perf_counter(), result.rounds[0], dict(receipts))

    def after_round(rounds: List[RoundObs], elapsed: float) -> None:
        if trace_dir and not traced[0] and elapsed >= TRACE_SECONDS:
            stop_trace(len(rounds))

    rounds, window_s = drive_window(one_round, seconds, after_round)
    if trace_dir and not traced[0]:
        stop_trace(len(rounds))
    n_traced = traced[0]
    compiles_in_window = clock.count() - compiles_before
    peak = common.peak_bytes(devices[0])

    # -- what the timed path produced, for the check ------------------------
    n_silos = len(silos)
    faults = [ev for ev in driver.trace
              if isinstance(ev, (RevocationOccurred, DeadlineExpired, StragglerEscalated))]
    failed = sum(n_silos - len(r.record.fold_times_s) for r in rounds) + len(faults)
    from bench import fl_reference as flr

    program_new = flr.host_leaves(driver.fold_reports[0].params)
    program_steps = [client_cls.first_steps[s.client_id].read() for s in silos]
    program_loss = float(rounds[0].record.metrics.get("loss", math.nan))
    msg = rounds[0].record.message_log
    driver.close()
    spans = list(client_cls.spans)
    del driver, clients
    gc.collect()

    run = Run(cfg=cfg, module=module, n_chips=wl["chips"],
              peaks=peaks, setup_s=setup_s, window_s=window_s, rounds=rounds,
              spans=spans, n_params=n_params)
    if trace_dir:
        t0 = time.perf_counter()
        try:
            run.trace = _reduce_trace(trace_dir, run, n_traced)
            trace_timing["reduce_s"] = time.perf_counter() - t0
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # -- metrics ----------------------------------------------------------
    metrics: Dict[str, Dict[str, Any]] = {}
    for entry in common.metrics_for(bench, workload_name, trace):
        value = common.metric(entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    if trace and run.trace is not None:
        run.trace.pop("ops")
        run.trace.pop("modules")

    # -- the check against the plain reference ------------------------------
    ref = flr.Reference(cfg, module)
    ref_steps = [ref.first_steps(params0, d["train"]) for d in data]
    reference = flr.reference_round(ref, params0, data, codec)
    numbers = {**flr.compare_steps(program_steps, ref_steps),
               **flr.compare(flr.host_leaves(params0), program_new,
                             flr.host_leaves(reference["params"]),
                             program_loss, reference["eval_loss"], ref_steps[0]["grad"])}
    # Every number is printed; those the cell gives a limit decide.
    checks = {name: {"value": v, "limit": limits.get(name)}
              for name, v in numbers.items() if isinstance(v, float)}
    correct = failed == 0 and all(
        math.isfinite(checks[name]["value"]) and checks[name]["value"] <= limit
        for name, limit in limits.items())

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    lines = [
        "device " + json.dumps({"platform": devices[0].platform, "kind": kind,
                                "count": len(devices)}),
        f"peak_hbm_bytes {peak}",
        f"compiles_in_window {compiles_in_window}",
        "round_message_bytes " + json.dumps({
            "s_msg_train": msg.s_msg_train_bytes, "c_msg_train": msg.c_msg_train_bytes,
            "s_msg_aggreg": msg.s_msg_aggreg_bytes, "c_msg_test": msg.c_msg_test_bytes}
            if msg is not None else None),
        "rounds " + json.dumps({"n": len(rounds), "traced": n_traced,
                                "wall_s": [r.wall_s for r in rounds]}),
        f"turnaround_samples {sum(len(r.receipts) for r in rounds)}",
        "trace_timing " + json.dumps(trace_timing),
        "first_step_losses " + json.dumps({"program": [r["loss"] for r in program_steps],
                                           "reference": [r["loss"] for r in ref_steps]}),
        "reference_numbers " + json.dumps(numbers),
    ]
    result_line: Dict[str, Any] = {
        "correct": bool(correct),
        "attempted": n_silos * len(rounds),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if run.trace is not None:
        result_line["breakdown"] = run.trace["breakdown"]
    result_line["checks"] = {**checks, "failed_updates": {"value": int(failed), "limit": 0}}
    return {"lines": lines, "result": result_line}
