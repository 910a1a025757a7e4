"""The readers of the program's spans and counters, on hand-made runs:
each per-round value, the idle share the wire spans cover on a synthetic
trace, and no value where the records carry no spans (a program that
does not record them)."""
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from bench import common, program_spans  # noqa: E402

SPAN_METRICS = ("d2h_s", "pack_s", "socket_s", "unpack_s", "h2d_s", "encode_s",
                "step_dispatch_s", "idle_wire_share")


def _span(name, where, start, dur, parent=None):
    return SimpleNamespace(name=name, where=where, round_idx=1, parent=parent,
                           start_s=start, dur_s=dur, nbytes=0, end_s=start + dur)


def _round(index, start, spans, counters):
    record = SimpleNamespace(spans=spans, counters=counters, fold_times_s={})
    return SimpleNamespace(index=index, start=start, end=start + 10.0, record=record)


def _run(with_spans=True):
    """Two rounds 10 s apart.  Round 1: the driver serializes 0.5-1.5 s
    and sends 1.5-2.0 s; silo c0 trains 2-6 s, encodes 6.0-6.4 s and
    its reply is received 6.4-7.0 s.  Round 2 doubles every counter."""
    rounds = []
    for i, t0 in ((1, 100.0), (2, 110.0)):
        spans = [
            _span("fl.round", "driver", t0, 10.0),
            _span("fl.serialize", "driver", t0 + 0.5, 1.0, 0),
            _span("fl.send", "driver", t0 + 1.5, 0.5, 0),
            _span("fl.recv", "driver", t0 + 6.4, 0.6, 0),
            _span("fl.job", "c0", t0 + 1.5, 5.0),
            _span("fl.train", "c0", t0 + 2.0, 4.0, 4),
            _span("fl.encode", "c0", t0 + 6.0, 0.4, 4),
        ] if with_spans else []
        counters = {"d2h_s": 0.3 * i, "pack_s": 0.2 * i, "unpack_s": 0.1 * i,
                    "h2d_s": 0.4 * i, "step_dispatch_s": 1.0 * i} if with_spans else {}
        rounds.append(_round(i, t0, spans, counters))
    # The trace: one round traced, 10 s from 0 ns on the trace's clock,
    # device busy 0-1 s and 2-6 s.
    ops = [("%fusion.1", 0.0, 1e9), ("%fusion.2", 2e9, 4e9)]
    trace = {"window_ns": (0.0, 10e9), "window_s": 10.0,
             "ops": {"/device:TPU:0": ops}, "rounds": rounds[:1]}
    return SimpleNamespace(rounds=rounds, trace=trace)


@pytest.mark.parametrize("name,expect", [
    ("d2h_s", 0.45), ("pack_s", 0.3), ("unpack_s", 0.15), ("h2d_s", 0.6),
    ("socket_s", 1.1), ("encode_s", 0.4), ("step_dispatch_s", 1.5),
])
def test_span_metric_reads_a_per_round_mean(name, expect):
    assert common.metric(name).read(_run()) == pytest.approx(expect)


def test_idle_wire_share_on_a_synthetic_trace():
    # Idle: 1-2 s and 6-10 s.  Wire spans: 0.5-2.0 s (serialize, send)
    # and 6.0-7.0 s (encode, recv).  Idle under them: 1 s + 1 s of 10 s.
    assert common.metric("idle_wire_share").read(_run()) == pytest.approx(20.0)


def test_idle_wire_share_needs_a_trace():
    run = _run()
    run.trace = None
    assert common.metric("idle_wire_share").read(run) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_no_value_without_spans(name):
    assert common.metric(name).read(_run(with_spans=False)) is None
    bare = _run()
    for r in bare.rounds:        # a RoundRecord with no span fields at all
        r.record = SimpleNamespace(fold_times_s={})
    assert common.metric(name).read(bare) is None


def test_overlap_of_sorted_intervals():
    a = [(0.0, 2.0), (5.0, 9.0)]
    b = [(1.0, 6.0), (8.0, 12.0)]
    assert program_spans.overlap_ns(a, b) == pytest.approx(1.0 + 1.0 + 1.0)
    assert program_spans.overlap_ns(a, []) == 0.0
