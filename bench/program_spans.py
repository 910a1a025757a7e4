"""Reads of the spans and counters the live round records on its own
``RoundRecord`` (``record.spans``, ``record.counters``; see
``repro.spans``).  Every read returns None where the records carry
none, as a program that does not record them.

Sums over threads are host seconds of work, not wall time: the driver's
and the silos' threads overlap, and they share one interpreter lock.
"""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence, Tuple

# Spans in which a thread moves the bytes of a message: codec, copies
# between host and device, sockets.
WIRE = ("fl.serialize", "fl.send", "fl.recv", "fl.deserialize", "fl.encode")


def records(rounds: Sequence) -> Optional[List]:
    """The rounds' RoundRecords, or None where any carries no spans."""
    recs = [r.record for r in rounds]
    if not recs or not all(getattr(rec, "spans", None) for rec in recs):
        return None
    return recs


def counter_per_round(run, name: str) -> Optional[float]:
    """Counter ``name``, summed over the driver and the silos, per round."""
    recs = records(run.rounds)
    if recs is None:
        return None
    return sum(rec.counters.get(name, 0.0) for rec in recs) / len(recs)


def span_s_per_round(run, names: Iterable[str]) -> Optional[float]:
    """Summed durations of the spans named, on every thread, per round;
    None where no such span was recorded."""
    recs = records(run.rounds)
    if recs is None:
        return None
    names = set(names)
    durs = [s.dur_s for rec in recs for s in rec.spans if s.name in names]
    return sum(durs) / len(recs) if durs else None


def intervals_ns(run, names: Iterable[str]) -> Optional[List[Tuple[float, float]]]:
    """The traced rounds' spans named, on the trace's clock: round 1's
    ``bench_round`` start (the traced window's start) pairs with its
    host-clock start."""
    rounds = run.trace["rounds"]
    recs = records(rounds)
    if recs is None:
        return None
    names = set(names)
    t0_ns, t0 = run.trace["window_ns"][0], rounds[0].start
    return [(t0_ns + (s.start_s - t0) * 1e9, t0_ns + (s.end_s - t0) * 1e9)
            for rec in recs for s in rec.spans if s.name in names]


def overlap_ns(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
