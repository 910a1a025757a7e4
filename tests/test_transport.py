"""Wall-clock socket transport: loopback round-trip equivalence vs the
in-process AsyncFLServer (same params, same trace vocabulary modulo
timestamps), §4.3 crash-mid-round recovery, reply-timeout mapping onto
exclusion + §4.4 StragglerEscalated, deadline carry-over on measured
arrivals, and the measured-message-size feedback into CostModel."""
import select
import socket
import struct
import threading
import time

import jax.numpy as jnp
import msgpack
import numpy as np
import pytest

from conftest import make_toy_app, make_toy_env
from repro.core import CostModel, Experiment
from repro.core.events import (
    DeadlineExpired,
    RevocationOccurred,
    RoundClosed,
    RoundDispatched,
    StragglerEscalated,
    UpdateArrived,
    UpdateFolded,
)
from repro.federated import (
    AsyncFLServer,
    DeterministicSchedule,
    FixedDeadline,
    FLClient,
    LiveRoundDriver,
    SocketTransport,
    ThreadWorkerPool,
)
from repro.federated.async_server import ArrivalSchedule, ClientArrival
from repro.federated.transport import MSG_C_TRAIN, _ConnState, recv_frame, send_frame
from repro.optim import make_optimizer


# ---------------------------------------------------------------------------
# Scenario helpers: real FLClients over a tiny linear model
# ---------------------------------------------------------------------------

class ArraySilo:
    """In-memory silo yielding (x, y) minibatches."""

    def __init__(self, client_id, x, y):
        self.client_id = client_id
        self.x = x
        self.y = y

    def batches(self, batch_size, split="train"):
        for i in range(0, len(self.x), batch_size):
            yield (self.x[i:i + batch_size], self.y[i:i + batch_size])


class PacedClient(FLClient):
    """Real FLClient with a controlled reply delay and crash injection.

    ``delay_s`` sleeps before training (so socket arrival order is
    deterministic) — a float, or a per-attempt sequence (last entry
    repeats); attempt numbers in ``crash_on_attempts`` raise out of
    train() — which, behind the socket transport, drops the connection:
    the §4.3 crash signal.  ``crash_eval_on_attempts`` does the same
    from evaluate() (an evaluation-phase crash)."""

    def __init__(self, *args, delay_s=0.0, crash_on_attempts=(),
                 crash_eval_on_attempts=(), **kwargs):
        super().__init__(*args, **kwargs)
        self.delay_s = delay_s
        self._crash_on = set(crash_on_attempts)
        self._crash_eval_on = set(crash_eval_on_attempts)
        self._attempts = 0
        self._eval_attempts = 0
        # Deterministic cross-silo ordering under any machine load:
        # a client acquires its semaphore before training and releases
        # the other's after — no sleep-based race.
        self.acquire_sem = None
        self.release_sem = None

    def train(self, global_params):
        self._attempts += 1
        if self._attempts in self._crash_on:
            raise RuntimeError("silo VM revoked (injected)")
        if self.acquire_sem is not None:
            assert self.acquire_sem.acquire(timeout=30.0)
            time.sleep(0.05)  # let the releaser's reply hit the wire first
        delay = self.delay_s
        if not isinstance(delay, (int, float)):
            delay = delay[min(self._attempts, len(delay)) - 1]
        if delay:
            time.sleep(delay)
        result = super().train(global_params)
        if self.release_sem is not None:
            self.release_sem.release()
        return result

    def evaluate(self, aggregated_params):
        self._eval_attempts += 1
        if self._eval_attempts in self._crash_eval_on:
            raise RuntimeError("silo VM revoked during evaluation (injected)")
        return super().evaluate(aggregated_params)


def _linear_loss(params, batch):
    x, y = batch
    pred = x @ params["w"]
    return jnp.mean((pred - y) ** 2)


def make_paced_clients(delays, crash_on=None, n_examples=(12, 20), seed=0):
    """Real FLClients (distinct silos/sizes) with deterministic pacing."""
    crash_on = crash_on or {}
    rng = np.random.default_rng(seed)
    clients = []
    for i, (cid, delay) in enumerate(delays.items()):
        n = n_examples[i % len(n_examples)]
        x = jnp.asarray(rng.standard_normal((n, 3)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((n,)), jnp.float32)
        clients.append(
            PacedClient(
                cid,
                ArraySilo(cid, x, y),
                _linear_loss,
                make_optimizer("sgdm", 1e-2),
                batch_size=8,
                delay_s=delay,
                crash_on_attempts=crash_on.get(cid, ()),
            )
        )
    return clients


def init_params():
    return {"w": jnp.zeros((3,), jnp.float32)}


def chain_replies(first, second):
    """Force `second`'s c_msg_train after `first`'s, every round, under
    any scheduler load: first releases a token per train, second
    acquires one before training."""
    sem = threading.Semaphore(0)
    first.release_sem = sem
    second.acquire_sem = sem


def chain_on_arrival(driver, first, second):
    """Like :func:`chain_replies`, with `first`'s token released when the
    driver polls `first`'s c_msg_train off its transport rather than when
    `first`'s train() returns: `second` trains only once that reply has
    arrived, however long `first` takes to encode, serialize and send.
    Call after ``serve()`` returns."""
    sem = threading.Semaphore(0)
    first.release_sem = None
    second.acquire_sem = sem
    poll = driver.transport.poll

    def polled(timeout):
        events = poll(timeout)
        for ev in events:
            if (ev.kind == "message" and ev.client_id == first.client_id
                    and ev.header.get("kind") == MSG_C_TRAIN):
                sem.release()
        return events

    driver.transport.poll = polled


def trace_signature(trace):
    """Event sequence modulo timestamps: (type, round, task, attempt)."""
    return [
        (
            type(e).__name__,
            getattr(e, "round_idx", None),
            getattr(e, "task", None),
            getattr(e, "attempt", None),
        )
        for e in trace
    ]


def assert_params_close(got, want):
    np.testing.assert_allclose(
        np.asarray(got["w"]), np.asarray(want["w"]), rtol=1e-5, atol=1e-6
    )


# ---------------------------------------------------------------------------
# Framing
# ---------------------------------------------------------------------------

def test_frame_roundtrip_over_socketpair():
    a, b = socket.socketpair()
    try:
        header = {"kind": "c_msg_train", "round_idx": 3, "n_samples": 17}
        payload = b"\x00\x01" * 513
        wire = send_frame(a, header, payload)
        got_header, got_payload = recv_frame(b)
        assert got_header == header
        assert got_payload == payload
        assert wire == 8 + (wire - 8 - len(payload)) + len(payload)
        a.close()
        assert recv_frame(b) is None  # clean EOF at a frame boundary
    finally:
        a.close()
        b.close()


def _wire(header, payload):
    """The frame layout, spelled out: u32 BE header and payload lengths,
    the msgpack header, the raw payload."""
    head = msgpack.packb(header, use_bin_type=True)
    return struct.pack(">II", len(head), len(payload)) + head + payload


_MSG = {"kind": "c_msg_train", "round_idx": 3, "client_id": "c0", "n_samples": 17}
_HELLO = {"kind": "hello", "client_id": "c0"}
_BIG = np.random.default_rng(7).integers(0, 256, 5 << 20, np.uint8).tobytes()


def _pieces(blob, *cuts):
    edges = [0, *cuts, len(blob)]
    return [blob[a:b] for a, b in zip(edges, edges[1:])]


# name -> (writes, expected frames, whether the peer closes mid-frame)
_RECV_CASES = {
    "prefix_split": (_pieces(_wire(_MSG, b"abc"), 3, 5), [(_MSG, b"abc")], False),
    "header_split": (_pieces(_wire(_MSG, b"abc"), 10, 14), [(_MSG, b"abc")], False),
    "payload_0": ([_wire(_MSG, b"")], [(_MSG, b"")], False),
    "payload_1": ([_wire(_MSG, b"\x07")], [(_MSG, b"\x07")], False),
    "payload_5mib": (_pieces(_wire(_MSG, _BIG), 5, 60, 1 << 20, 3 << 20),
                     [(_MSG, _BIG)], False),
    "two_frames_one_write": ([_wire(_MSG, b"one") + _wire({**_MSG, "round_idx": 4}, b"two")],
                             [(_MSG, b"one"), ({**_MSG, "round_idx": 4}, b"two")], False),
    "hello_then_message": ([_wire(_HELLO, b"") + _wire(_MSG, b"xyz")],
                           [(_HELLO, b""), (_MSG, b"xyz")], False),
    "eof_mid_prefix": ([_wire(_MSG, b"abc")[:5]], [], True),
    "eof_mid_payload": ([_wire(_MSG, b"one"), _wire(_MSG, _BIG)[: 2 << 20]],
                        [(_MSG, b"one")], True),
    "clean_eof": ([_wire(_MSG, b"abc"), _wire(_MSG, b"")],
                  [(_MSG, b"abc"), (_MSG, b"")], False),
}


def _dribble(sock, writes):
    """Write each piece on its own, a pause apart, then close."""
    def run():
        for piece in writes:
            sock.sendall(piece)
            time.sleep(0.005)
        sock.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def _read_with_recv_frame(sock, n_frames):
    frames = [recv_frame(sock) for _ in range(n_frames)]
    try:
        end = "clean" if recv_frame(sock) is None else "frame"
    except ConnectionError:
        end = "mid-frame"
    return frames, end


def _read_with_driver(sock, hello):
    # The driver's nonblocking reader.  Without a hello in the case the
    # connection is taken as already identified.
    transport = SocketTransport()
    sock.setblocking(False)
    state = _ConnState(sock)
    if not hello:
        state.client_id = "c0"
    events = []
    deadline = time.monotonic() + 30.0
    while not events or events[-1].kind != "disconnect":
        assert time.monotonic() < deadline
        select.select([sock], [], [], 1.0)
        transport._read(state, events)
    assert all(ev.client_id == "c0" for ev in events)
    return [(_HELLO, b"") if ev.kind == "joined" else (ev.header, ev.payload)
            for ev in events[:-1]]


@pytest.mark.parametrize("path", ["recv_frame", "driver"])
@pytest.mark.parametrize("case", list(_RECV_CASES))
def test_frame_reads_in_pieces(case, path):
    """Frames written in pieces, or several to a write, come out whole on
    the worker's blocking reader and the driver's nonblocking one; an
    EOF inside a frame is an error (a disconnect, to the driver)."""
    writes, want, mid_frame = _RECV_CASES[case]
    a, b = socket.socketpair()
    try:
        writer = _dribble(a, writes)
        if path == "recv_frame":
            got, end = _read_with_recv_frame(b, len(want))
            assert end == ("mid-frame" if mid_frame else "clean")
        else:
            got = _read_with_driver(b, hello=bool(want) and want[0][0] == _HELLO)
        writer.join(timeout=10.0)
        assert len(got) == len(want)
        for (header, payload), (want_header, want_payload) in zip(got, want):
            assert header == want_header
            assert len(payload) == len(want_payload)
            assert payload == want_payload
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize("timeout", [None, 10.0], ids=["blocking", "timeout"])
@pytest.mark.parametrize("size", [300, 3 << 20], ids=["small", "3mib"])
def test_send_frame_writes_the_frame_layout(size, timeout):
    # With a timeout (the driver's sends) the socket takes a frame in
    # partial writes; blocking (the silos'), in one.
    payload = bytes(range(256)) * (size // 256) + b"\x01" * (size % 256)
    header = {"kind": "s_msg_train", "round_idx": 1}
    a, b = socket.socketpair()
    a.settimeout(timeout)
    try:
        sent = []
        t = threading.Thread(target=lambda: (sent.append(send_frame(a, header, payload)),
                                             a.close()), daemon=True)
        t.start()
        want = _wire(header, payload)
        raw = bytearray()
        while len(raw) <= len(want) and (chunk := b.recv(1 << 20)):
            raw += chunk
        assert bytes(raw) == want
        t.join(timeout=10.0)
        assert not t.is_alive()
        assert sent == [len(raw)]
    finally:
        a.close()
        b.close()


def test_transport_requires_start():
    transport = SocketTransport()
    with pytest.raises(RuntimeError):
        _ = transport.address
    with pytest.raises(RuntimeError):
        transport.poll(0.0)


# ---------------------------------------------------------------------------
# Loopback round-trip equivalence vs the in-process driver
# ---------------------------------------------------------------------------

def test_loopback_run_matches_in_process_async_server():
    """The acceptance scenario: a builder-chained loopback run over two
    real FLClient workers produces the same final params and the same
    event sequence (modulo wall-clock timestamps) as the in-process
    AsyncFLServer on the same scenario."""
    delays = {"c0": 0.0, "c1": 0.0}
    clients = make_paced_clients(delays)
    chain_replies(clients[0], clients[1])  # c0's reply always lands first
    driver = Experiment().transport(reply_timeout_s=30.0).serve(
        clients, init_params()
    )
    assert isinstance(driver, LiveRoundDriver)
    with driver:
        live = driver.run(2)

    # Same clients, same initial params, arrivals modeled instead of
    # measured: the virtual-clock sibling of the exact same scenario.
    server = AsyncFLServer(
        clients,
        init_params(),
        schedule=DeterministicSchedule({"c0": 0.01, "c1": 0.02}),
    )
    sim = server.run(2)

    assert_params_close(live.final_params, sim.final_params)
    assert trace_signature(driver.trace) == trace_signature(server.bus.trace)
    for rec_live, rec_sim in zip(live.rounds, sim.rounds):
        assert rec_live.metrics.keys() == rec_sim.metrics.keys()
        assert rec_live.metrics["loss"] == pytest.approx(
            rec_sim.metrics["loss"], rel=1e-4
        )
    # The live records carry measured fold times for every silo.
    assert set(live.rounds[0].fold_times_s) == {"c0", "c1"}


def test_loopback_survives_injected_crash_via_rerequest():
    """§4.3: a worker that dies mid-round is restarted, its retrained
    update re-requested — the round still averages every silo, and the
    trace shows RevocationOccurred + an attempt-2 arrival, exactly like
    the in-process engine replaying the same revocation."""
    delays = {"c0": 0.0, "c1": 0.0}
    clients = make_paced_clients(delays, crash_on={"c1": (1,)})
    chain_replies(clients[0], clients[1])  # c1's re-request lands after c0
    driver = Experiment().transport(reply_timeout_s=30.0).serve(
        clients, init_params()
    )
    with driver:
        live = driver.run(2)

    class RevokeOnceSchedule(ArrivalSchedule):
        def round_arrivals(self, round_idx, client_ids):
            out = {"c0": ClientArrival("c0", 0.01),
                   "c1": ClientArrival("c1", 0.05)}
            if round_idx == 1:
                out["c1"] = ClientArrival("c1", 0.05, revoke_at_s=0.02)
            return {cid: out[cid] for cid in client_ids}

    server = AsyncFLServer(
        clients, init_params(), schedule=RevokeOnceSchedule(),
        on_revocation="rerequest",
    )
    sim = server.run(2)

    assert driver.fold_reports[0].rerequested == ["c1"]
    assert not driver.fold_reports[0].excluded
    assert "c1" in driver.cohort  # recovered silo stays in the run
    assert_params_close(live.final_params, sim.final_params)
    assert trace_signature(driver.trace) == trace_signature(server.bus.trace)
    revs = [e for e in driver.trace if isinstance(e, RevocationOccurred)]
    assert [e.task for e in revs] == ["c1"]
    arrivals = [e for e in driver.trace
                if isinstance(e, UpdateArrived) and e.task == "c1"]
    assert [e.attempt for e in arrivals] == [2, 1]  # round 1 re-request


def test_crash_with_exhausted_budget_excludes_and_drops_from_cohort():
    delays = {"c0": 0.0, "c1": 0.1}
    clients = make_paced_clients(delays, crash_on={"c1": (1, 2)})
    driver = Experiment().transport(
        reply_timeout_s=30.0, max_rerequests=1
    ).serve(clients, init_params())
    with driver:
        live = driver.run(2)
    report = driver.fold_reports[0]
    assert report.excluded == ["c1"]
    assert driver.cohort == ["c0"]  # terminal crash leaves the run
    # Round 2 dispatches to the survivor only.
    dispatches = [e for e in driver.trace if isinstance(e, RoundDispatched)]
    assert [e.n_clients for e in dispatches] == [2, 1]
    assert len(live.rounds) == 2


def test_reply_timeout_maps_to_recovery_and_straggler_escalation():
    """A silent silo becomes a §4.3 suspected fault for the round
    (RevocationOccurred, excluded from the fold) but stays in the
    cohort; consecutive timeouts escalate through the engine's shared
    StragglerTracker as §4.4 StragglerEscalated + on_straggler."""
    escalated = []
    delays = {"c0": 0.0, "c1": 1.5}
    clients = make_paced_clients(delays)
    driver = Experiment().transport(reply_timeout_s=0.4).serve(
        clients,
        init_params(),
        escalate_after=1,
        on_straggler=lambda cid, r: escalated.append((cid, r)),
    )
    with driver:
        live = driver.run(1)
    assert driver.fold_reports[0].excluded == ["c1"]
    assert driver.cohort == ["c0", "c1"]  # merely slow: stays in the run
    revs = [e for e in driver.trace if isinstance(e, RevocationOccurred)]
    assert [e.task for e in revs] == ["c1"]
    escs = [e for e in driver.trace if isinstance(e, StragglerEscalated)]
    assert [(e.task, e.consecutive_misses) for e in escs] == [("c1", 1)]
    assert escalated == [("c1", 1)]
    # Only the on-time silo is in the round's average.
    folded = [e.task for e in driver.trace if isinstance(e, UpdateFolded)]
    assert folded == ["c0"]
    assert len(live.rounds) == 1


def test_deadline_policy_parks_measured_late_arrival_for_next_round():
    """RoundDeadline policies run unchanged on measured arrivals: a
    reply that lands after T_round is parked and folds stale (with the
    carry discount) into the next round — never dropped."""
    delays = {"c0": 0.0, "c1": 0.6}
    clients = make_paced_clients(delays)
    driver = Experiment().async_rounds(
        deadline=FixedDeadline(t_round_s=0.3, min_clients=1)
    ).transport().serve(clients, init_params())
    with driver:
        live = driver.run(2)
    first, second = driver.fold_reports
    assert first.carried_over == ["c1"]
    assert second.carried_in == ["c1"]
    assert live.rounds[0].carried_over == ["c1"]
    assert live.rounds[1].carried_in == ["c1"]
    stale = [e for e in driver.trace
             if isinstance(e, UpdateFolded) and e.origin_round is not None]
    assert [(e.task, e.origin_round, e.round_idx) for e in stale] == [
        ("c1", 1, 2)
    ]
    deadlines = [e for e in driver.trace if isinstance(e, DeadlineExpired)]
    assert deadlines and deadlines[0].late == ("c1",)
    closed = [e for e in driver.trace if isinstance(e, RoundClosed)]
    assert closed[0].carried_over == ("c1",) and closed[1].carried_in == ("c1",)


# ---------------------------------------------------------------------------
# Measured message sizes -> CostModel (Eq. 6 on real payloads)
# ---------------------------------------------------------------------------

def test_measured_message_sizes_feed_cost_model():
    env = make_toy_env()
    app = make_toy_app()
    cm = CostModel(env, app, 0.5)
    cost_max_before = cm.cost_max()
    delays = {"c0": 0.0, "c1": 0.05}
    clients = make_paced_clients(delays)
    driver = Experiment().transport(reply_timeout_s=30.0).serve(
        clients, init_params(), cost_model=cm
    )
    with driver:
        live = driver.run(1)
    log = live.rounds[0].message_log
    assert log is not None
    # Weight payloads measured from the actual serialized pytree, and
    # the metrics payload measured from the actual serialized dict.
    assert log.s_msg_train_bytes == log.s_msg_aggreg_bytes > 0
    assert log.c_msg_train_bytes == log.s_msg_train_bytes
    assert 0 < log.c_msg_test_bytes < log.s_msg_train_bytes
    assert cm.app.messages.s_msg_train_gb == pytest.approx(
        log.s_msg_train_bytes / 1e9
    )
    assert cm.app.messages.c_msg_test_gb == pytest.approx(
        log.c_msg_test_bytes / 1e9
    )
    assert cm.cost_max() != cost_max_before  # Eq.-7 cache invalidated


# ---------------------------------------------------------------------------
# Builder surface
# ---------------------------------------------------------------------------

def test_builder_transport_validation():
    with pytest.raises(ValueError, match="kind"):
        Experiment().transport(kind="carrier-pigeon")
    with pytest.raises(ValueError, match="on_revocation"):
        Experiment().transport(on_revocation="retry-forever")
    with pytest.raises(ValueError, match="reply_timeout_s"):
        Experiment().transport(reply_timeout_s=0.0)
    with pytest.raises(ValueError, match="max_rerequests"):
        Experiment().transport(max_rerequests=-1)


def test_builder_rejects_schedule_with_transport():
    clients = make_paced_clients({"c0": 0.0})
    with pytest.raises(ValueError, match="virtual-clock"):
        Experiment().transport().serve(
            clients, init_params(), schedule=DeterministicSchedule(0.0)
        )


def test_builder_transport_worker_kind_type_guards():
    clients = make_paced_clients({"c0": 0.0})
    with pytest.raises(TypeError, match="factory"):
        Experiment().transport(kind="process").serve(clients, init_params())
    with pytest.raises(TypeError, match="FLClient objects"):
        Experiment().transport(kind="thread").serve(
            {"c0": lambda: clients[0]}, init_params()
        )
    with pytest.raises(TypeError, match="transport"):
        Experiment().serve({"c0": lambda: clients[0]}, init_params())


def test_builder_chains_do_not_alias_transport():
    base = Experiment()
    with_transport = base.transport()
    assert base._transport is None
    assert with_transport._transport is not None
    # A later setter on the transported chain keeps the transport.
    assert with_transport.rounds(3)._transport is not None


# ---------------------------------------------------------------------------
# Worker pool plumbing
# ---------------------------------------------------------------------------

def test_thread_pool_rejects_duplicate_ids():
    clients = make_paced_clients({"c0": 0.0})
    with pytest.raises(ValueError, match="duplicate"):
        ThreadWorkerPool(clients + clients, init_params())


@pytest.mark.parametrize("backend", ["tpu", "gpu"])
def test_process_pool_refuses_an_accelerator_backend(monkeypatch, backend):
    """A spawned silo cannot reach a chip the parent already holds: off
    the CPU the process pool refuses to start and points at threads."""
    from repro.federated import transport

    monkeypatch.setattr(transport.jax, "default_backend", lambda: backend)
    factories = {"c0": lambda: None}
    with pytest.raises(RuntimeError, match=r'transport\(kind="thread"\)'):
        transport.ProcessWorkerPool(factories, init_params())
    with pytest.raises(RuntimeError, match=backend):
        Experiment().transport(kind="process").serve(factories, init_params())


def test_non_consecutive_timeouts_do_not_escalate():
    """An on-time reply clears the timeout-miss streak even without a
    RoundDeadline configured — two timeouts with an on-time round in
    between are not 'consecutive' (the StragglerTracker contract)."""
    delays = {"c0": 0.0, "c1": 0.0}
    clients = make_paced_clients(delays)
    clients[1].delay_s = [1.2, 0.0, 1.2]  # timeout, on-time, timeout
    driver = Experiment().transport(reply_timeout_s=0.7).serve(
        clients, init_params(), escalate_after=2
    )
    with driver:
        driver.run(3)
    assert [bool(r.excluded) for r in driver.fold_reports] == [
        True, False, True
    ]
    escs = [e for e in driver.trace if isinstance(e, StragglerEscalated)]
    assert escs == []  # round-2 delivery reset the streak
    assert driver._engine.stragglers.streak_of("c1") == 1


def test_eval_phase_crash_restarts_worker_and_keeps_silo():
    """A crash during the evaluation phase skips that round's metrics
    for the silo but restarts its worker — the silo stays in the cohort
    and trains again next round (§4.3 replacement, not silent drop)."""
    delays = {"c0": 0.0, "c1": 0.1}
    clients = make_paced_clients(delays)
    clients[1]._crash_eval_on = {1}
    driver = Experiment().transport(reply_timeout_s=30.0).serve(
        clients, init_params()
    )
    with driver:
        live = driver.run(2)
    assert driver.cohort == ["c0", "c1"]
    assert set(live.rounds[0].fold_times_s) == {"c0", "c1"}
    assert set(live.rounds[1].fold_times_s) == {"c0", "c1"}
    # Both rounds still produced aggregated metrics (round 1 from the
    # survivor alone).
    assert live.rounds[0].metrics and live.rounds[1].metrics


def test_crash_recovery_overrunning_reply_window_is_not_a_strike():
    """A silo whose §4.3 recovery is what overran reply_timeout_s is
    excluded from the round but NOT counted as a §4.4 straggler miss:
    the replacement destroyed the slow-silo evidence."""
    delays = {"c0": 0.0, "c1": 0.0}
    clients = make_paced_clients(delays, crash_on={"c1": (1,)})
    clients[1].delay_s = 1.5  # the retrain after restart overruns 0.6s
    driver = Experiment().transport(reply_timeout_s=0.6).serve(
        clients, init_params(), escalate_after=1
    )
    with driver:
        driver.run(1)
    assert driver.fold_reports[0].excluded == ["c1"]
    escs = [e for e in driver.trace if isinstance(e, StragglerEscalated)]
    assert escs == []
    assert driver._engine.stragglers.streak_of("c1") == 0
    revs = [e for e in driver.trace if isinstance(e, RevocationOccurred)]
    assert [e.task for e in revs] == ["c1"]


# Module-level factories: multiprocessing spawn pickles them by
# reference and rebuilds the clients inside the child process.
def _process_client_c0():
    return make_paced_clients({"c0": 0.0}, seed=0)[0]


def _process_client_c1():
    return make_paced_clients({"c1": 0.0}, seed=1)[0]


@pytest.mark.slow
def test_process_worker_pool_round_trip():
    """kind='process': real OS processes build their FLClient from a
    picklable factory and speak the same wire protocol."""
    driver = Experiment().transport(
        kind="process", reply_timeout_s=180.0, startup_timeout_s=120.0
    ).serve(
        {"c0": _process_client_c0, "c1": _process_client_c1}, init_params()
    )
    with driver:
        live = driver.run(1)
    assert len(live.rounds) == 1
    assert set(live.rounds[0].fold_times_s) == {"c0", "c1"}
    assert trace_signature(driver.trace)[0][0] == "RoundDispatched"
    folded = {e.task for e in driver.trace if isinstance(e, UpdateFolded)}
    assert folded == {"c0", "c1"}


def test_driver_restarts_are_bounded_by_cohort(monkeypatch):
    """restart() returning False (no replacement capacity) maps the
    crash onto exclusion instead of hanging the round."""
    delays = {"c0": 0.0, "c1": 0.1}
    clients = make_paced_clients(delays, crash_on={"c1": (1,)})
    pool = ThreadWorkerPool(clients, init_params())
    monkeypatch.setattr(pool, "restart", lambda cid, addr, host=None: False)
    driver = LiveRoundDriver(pool, init_params(), reply_timeout_s=30.0)
    with driver:
        live = driver.run(1)
    assert driver.fold_reports[0].excluded == ["c1"]
    assert driver.cohort == ["c0"]
    assert len(live.rounds) == 1
