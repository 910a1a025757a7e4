"""Spans and counters of the live round (``repro.spans``): the recorder's
semantics, and the span tree a loopback round records on its
``RoundRecord`` — dense and int8 messages of several MB, two silos."""
import collections
import socket
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.core import Experiment
from repro.optim import make_optimizer
from repro.federated import SocketTransport
from repro.federated.transport import _ConnState
from test_transport import ArraySilo, PacedClient, _wire, chain_on_arrival, trace_signature

# ---------------------------------------------------------------------------
# The recorder
# ---------------------------------------------------------------------------


def test_no_bound_log_records_nothing():
    with spans.span("fl.serialize") as sp:
        sp.nbytes = 10
    spans.add("pack_s", 1.0)
    assert spans.bound() is None


def test_nesting_sets_parent_and_counters_sum():
    log = spans.SpanLog("driver", 3)
    with spans.collect(log):
        with spans.span("fl.round"):
            with spans.span("fl.dispatch"):
                with spans.span("fl.serialize") as sp:
                    sp.nbytes = 7
                    spans.add("pack_s", 0.25)
                    spans.add("pack_s", 0.5)
            with spans.span("fl.collect"):
                pass
    assert [(s.name, s.parent) for s in log.spans] == [
        ("fl.round", None), ("fl.dispatch", 0), ("fl.serialize", 1), ("fl.collect", 0)]
    assert all(s.where == "driver" and s.round_idx == 3 for s in log.spans)
    assert log.spans[2].nbytes == 7
    assert log.counters == {"pack_s": 0.75}
    outer, inner = log.spans[0], log.spans[2]
    assert outer.start_s <= inner.start_s and inner.end_s <= outer.end_s
    assert spans.bound() is None


def test_a_log_is_bound_to_one_thread():
    log = spans.SpanLog()
    seen = []

    def other():
        seen.append(spans.bound())
        with spans.span("fl.send"):
            spans.add("send_bytes", 1)

    with spans.collect(log):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10.0)
    assert not t.is_alive()
    assert seen == [None] and log.spans == [] and log.counters == {}


def test_recv_reads_merge_within_the_gap_or_when_joined():
    log = spans.SpanLog()
    with spans.collect(log), spans.span("fl.collect"):
        for n in (3, 4):                    # back to back: one span
            with spans.span("fl.recv", n, merge_gap_s=spans.RECV_MERGE_S):
                pass
        with spans.span("fl.deserialize"):  # another span in between
            pass
        with spans.span("fl.recv", 5, merge_gap_s=float("inf")):
            pass
        with spans.span("fl.recv", 6, merge_gap_s=0.0):  # a new frame, no merge
            pass
        with spans.span("fl.recv", 1, merge_gap_s=float("inf")):  # continues it
            pass
    assert [(s.name, s.nbytes) for s in log.spans] == [
        ("fl.collect", 0), ("fl.recv", 7), ("fl.deserialize", 0), ("fl.recv", 5), ("fl.recv", 7)]


class _CountingSocket:
    """A socket whose ``recv_into`` calls are counted."""

    def __init__(self, sock):
        self.sock = sock
        self.calls = 0

    def recv_into(self, buf):
        self.calls += 1
        return self.sock.recv_into(buf)


def test_a_frame_read_in_many_reads_is_one_recv_span():
    frame = _wire({"kind": "c_msg_train", "round_idx": 1}, bytes(range(256)) * 4096)
    pieces = [frame[i:i + 100_000] for i in range(0, len(frame), 100_000)]
    a, b = socket.socketpair()
    b.setblocking(False)
    sock = _CountingSocket(b)
    state = _ConnState(sock)
    state.client_id = "c0"
    transport = SocketTransport()
    log, events, calls = spans.SpanLog(), [], 0
    try:
        with spans.collect(log):
            for piece in pieces:     # a read per piece, further apart than RECV_MERGE_S
                a.sendall(piece)
                time.sleep(3 * spans.RECV_MERGE_S)
                transport._read(state, events)
                calls += 1
    finally:
        a.close()
        b.close()
    assert calls == len(pieces) > 5
    assert [ev.kind for ev in events] == ["message"] and len(events[0].payload) == 1 << 20
    assert [(s.name, s.nbytes) for s in log.spans] == [("fl.recv", len(frame))]
    assert log.counters["recv_bytes"] == len(frame)
    assert 1 <= log.counters["recv_reads"] <= sock.calls


def test_back_dated_span_and_merge_reindex_parents():
    frame = spans.SpanLog()
    with spans.collect(frame), spans.span("fl.recv", 9):
        pass
    job = spans.SpanLog("c0", 2)
    with spans.collect(job), spans.span("fl.job", start_s=frame.spans[0].start_s):
        job.merge(frame.to_wire(), parent=0)
        with spans.span("fl.train"):
            spans.add("step_calls", 4)
    assert [(s.name, s.parent, s.where) for s in job.spans] == [
        ("fl.job", None, "c0"), ("fl.recv", 0, "c0"), ("fl.train", 0, "c0")]
    assert job.spans[0].start_s == frame.spans[0].start_s

    driver = spans.SpanLog("driver", 2)
    with spans.collect(driver), spans.span("fl.round"):
        driver.merge(job.to_wire(), where="c0", round_idx=2)
    assert [(s.name, s.parent) for s in driver.spans] == [
        ("fl.round", None), ("fl.job", None), ("fl.recv", 1), ("fl.train", 1)]
    assert driver.counters == {"step_calls": 4}


# ---------------------------------------------------------------------------
# A loopback round at several MB a message
# ---------------------------------------------------------------------------

WIDTH = 1 << 14       # w: 64 x 16384 fp32 = 4 MiB per message


def _loss(params, batch):
    x, y = batch
    return jnp.mean((jnp.tanh(x @ params["w"]) @ params["v"] - y) ** 2)


def _params():
    return {"w": jnp.full((64, WIDTH), 0.01, jnp.float32), "v": jnp.zeros((WIDTH,), jnp.float32)}


def _clients(codec):
    rng = np.random.default_rng(0)
    clients = []
    for cid, n in (("c0", 24), ("c1", 40)):
        x = rng.standard_normal((n, 64)).astype(np.float32)
        y = rng.standard_normal((n,)).astype(np.float32)
        clients.append(PacedClient(cid, ArraySilo(cid, x, y), _loss, make_optimizer("sgdm", 1e-2),
                                   batch_size=8, compression=codec))
    return clients


def _run(codec, n_rounds=2):
    exp = Experiment().transport(reply_timeout_s=60.0)
    if codec is not None:
        exp = exp.aggregation(compression=codec)
    clients = _clients(codec)
    driver = exp.serve(clients, _params())
    chain_on_arrival(driver, *clients)   # c0's reply always lands first
    with driver:
        result = driver.run(n_rounds)
    return driver, result


@pytest.fixture(scope="module", params=[None, "int8"], ids=["dense", "int8"])
def live(request):
    return request.param, *_run(request.param)


def _children(rec, idx):
    return [s.name for s in rec.spans if s.parent == idx]


def test_round_records_the_span_tree(live):
    codec, _, result = live
    for rec in result.rounds:
        driver = [i for i, s in enumerate(rec.spans) if s.where == "driver"]
        roots = [i for i in driver if rec.spans[i].parent is None]
        assert [rec.spans[i].name for i in roots] == ["fl.round"]
        top = {rec.spans[i].name: i for i in driver if rec.spans[i].parent == roots[0]}
        assert _children(rec, roots[0]) == [
            "fl.dispatch", "fl.collect", "fl.fold", "fl.fanout", "fl.collect_eval"]
        for phase in ("fl.dispatch", "fl.fanout"):
            assert _children(rec, top[phase]) == ["fl.serialize", "fl.send", "fl.send"]
        collect = _children(rec, top["fl.collect"])
        assert collect.count("fl.deserialize") == 2 and set(collect) == {"fl.recv", "fl.deserialize"}
        assert set(_children(rec, top["fl.collect_eval"])) == {"fl.recv"}
        assert set(_children(rec, top["fl.fold"])) <= {"fl.serialize"}
        for cid in ("c0", "c1"):
            jobs = [i for i, s in enumerate(rec.spans) if s.where == cid and s.name == "fl.job"]
            assert len(jobs) == 2 and all(rec.spans[i].parent is None for i in jobs)
            train, evaluate = jobs
            want = ["fl.recv", "fl.deserialize", "fl.train"]
            want += ["fl.encode", "fl.serialize"] if codec else ["fl.serialize"]
            assert _children(rec, train) == want
            train_step = next(i for i, s in enumerate(rec.spans)
                              if s.parent == train and s.name == "fl.train")
            assert _children(rec, train_step) == ["fl.drain"]
            assert _children(rec, evaluate) == ["fl.recv", "fl.deserialize", "fl.evaluate"]
            assert all(s.round_idx == rec.round_idx for s in rec.spans)


def test_silo_spans_lie_inside_the_round(live):
    _, _, result = live
    for rec in result.rounds:
        rnd = rec.spans[0]
        assert rnd.name == "fl.round" and rnd.dur_s > 0
        for s in rec.spans:
            assert rnd.start_s <= s.start_s and s.end_s <= rnd.end_s, s
            assert s.dur_s >= 0


def test_byte_counters_match_the_message_log(live):
    codec, _, result = live
    for rec in result.rounds:
        log, c = rec.message_log, rec.counters
        assert log.s_msg_train_bytes > 4 << 20
        assert c["send_bytes"] >= 2 * (log.s_msg_train_bytes + log.s_msg_aggreg_bytes)
        assert c["recv_bytes"] >= 2 * (log.c_msg_train_bytes + log.s_msg_train_bytes
                                       + log.s_msg_aggreg_bytes)
        sends = [s.nbytes for s in rec.spans if s.name == "fl.send"]
        assert sum(sends) == c["send_bytes"]
        assert sum(s.nbytes for s in rec.spans if s.name == "fl.recv") == c["recv_bytes"]
        # Eight frames a round (four each way), each read as its prefix
        # and then the rest: at least two reads a frame.
        assert 2 * 8 <= c["recv_reads"] <= c["recv_bytes"]
        # H2D: each silo's two received weight sets, and the driver's
        # dense replies or the int8 payloads its fold moved.  D2H: the
        # driver's two messages, and the silos' weights or int8 codes.
        n = (64 + 1) * WIDTH
        assert c["h2d_bytes"] >= 2 * 2 * 4 * n + (2 * n if codec else 2 * 4 * n)
        assert c["d2h_bytes"] >= 2 * 4 * n + (2 * n if codec else 2 * 4 * n)
        assert c.get("encode_device", 0) == (2 if codec else 0)
        assert c["step_calls"] == 3 + 5
        for name in ("d2h_s", "pack_s", "unpack_s", "h2d_s", "step_dispatch_s"):
            assert c[name] > 0, name


def test_span_counts_stay_bounded(live):
    _, _, result = live
    for rec in result.rounds:
        per_where = collections.Counter(s.where for s in rec.spans)
        assert per_where.pop("driver") <= 16 + 4 * 2
        assert set(per_where) == {"c0", "c1"}
        assert all(n <= 16 for n in per_where.values())


class _Unbound:
    def __init__(self, log):
        pass

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return None


def test_bus_trace_is_the_same_without_spans(live, monkeypatch):
    codec, driver, _ = live
    monkeypatch.setattr(spans, "collect", _Unbound)
    bare_driver, bare = _run(codec)
    assert all(not r.spans and not r.counters for r in bare.rounds)
    assert trace_signature(driver.trace) == trace_signature(bare_driver.trace)
