"""Spans and counters of the live FL round, on the host's clock.

A :class:`SpanLog` holds span records and counters.  :func:`collect`
binds a log to the calling thread; while one is bound, :func:`span`
appends a record for each interval it times and :func:`add` sums into a
counter.  With no log bound both are cheap no-ops apart from the
profiler annotation, so the same code serves the in-process drivers,
which record nothing.

Every span is also a ``jax.profiler.TraceAnnotation`` of the same name,
so a profile shows it on the host rows above the device's ops.  Records
are stamped with ``time.perf_counter()`` (``CLOCK_MONOTONIC`` on Linux,
shared by every process of the host).  A record's ``parent`` is the
index, in the same log, of the span that was open around it when it
started (None for a root).

Nothing here touches the control-plane ``EventBus``: spans describe
where the host's time went, never what the protocol did.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Mapping, Optional, Type

import jax

# Consecutive socket reads closer than this merge into one fl.recv span.
RECV_MERGE_S = 1e-3

_bound = threading.local()


@dataclasses.dataclass
class Span:
    """One timed interval: ``start_s`` on ``time.perf_counter()``."""

    name: str
    where: str
    round_idx: int
    parent: Optional[int]
    start_s: float
    dur_s: float = 0.0
    nbytes: int = 0

    @property
    def end_s(self) -> float:
        return self.start_s + self.dur_s


class SpanLog:
    """The spans and counters of one thread's share of a round: the
    driver's (``where="driver"``) or one silo job's (its client id)."""

    def __init__(self, where: str = "driver", round_idx: int = 0) -> None:
        self.where = where
        self.round_idx = round_idx
        self.spans: List[Span] = []
        self.counters: Dict[str, float] = {}
        self._open: List[int] = []   # indices of the spans entered, not yet left

    def to_wire(self) -> Dict[str, Any]:
        """msgpack-able form, for a worker's reply header."""
        return {
            "spans": [[s.name, s.parent, s.start_s, s.dur_s, s.nbytes] for s in self.spans],
            "counters": dict(self.counters),
        }

    def merge(self, wire: Mapping[str, Any], where: Optional[str] = None,
              round_idx: Optional[int] = None, parent: Optional[int] = None) -> None:
        """Append another log's records (in :meth:`to_wire` form): their
        roots hang under ``parent``, their own parents are re-indexed,
        and their counters are summed into this log's."""
        offset = len(self.spans)
        where = self.where if where is None else where
        round_idx = self.round_idx if round_idx is None else round_idx
        for name, par, start, dur, nbytes in wire.get("spans", ()):
            self.spans.append(Span(str(name), where, int(round_idx),
                                   parent if par is None else offset + int(par),
                                   float(start), float(dur), int(nbytes)))
        for name, value in wire.get("counters", {}).items():
            self.counters[name] = self.counters.get(name, 0) + value


class collect:
    """Bind ``log`` to the calling thread for the ``with`` block."""

    def __init__(self, log: SpanLog) -> None:
        self.log = log
        self._prev: Optional[SpanLog] = None

    def __enter__(self) -> SpanLog:
        self._prev = getattr(_bound, "log", None)
        _bound.log = self.log
        return self.log

    def __exit__(self, *exc: Any) -> None:
        _bound.log = self._prev


def bound() -> Optional[SpanLog]:
    """The log bound to the calling thread, if any."""
    return getattr(_bound, "log", None)


class span:
    """Time the ``with`` block as span ``name``.  Set ``.nbytes`` inside
    the block where the size is known only at its end.

    ``start_s`` back-dates the record (a job whose frame began arriving
    on another thread).  ``merge_gap_s`` folds the block into the log's
    last record when that is a span of the same name under the same
    parent that ended less than ``merge_gap_s`` before this one starts:
    its end moves out and the bytes add up."""

    __slots__ = ("name", "nbytes", "_start", "_merge_gap", "_log", "_rec", "_ann")

    def __init__(self, name: str, nbytes: int = 0, start_s: Optional[float] = None,
                 merge_gap_s: float = 0.0) -> None:
        self.name = name
        self.nbytes = nbytes
        self._start = start_s
        self._merge_gap = merge_gap_s
        self._log: Optional[SpanLog] = None
        self._rec: Optional[Span] = None

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        log = self._log = getattr(_bound, "log", None)
        if log is not None:
            now = time.perf_counter()
            parent = log._open[-1] if log._open else None
            last = log.spans[-1] if log.spans else None
            if (last is not None and last.name == self.name and last.parent == parent
                    and now - last.end_s < self._merge_gap):
                self._rec = last
                log._open.append(len(log.spans) - 1)
            else:
                self._rec = Span(self.name, log.where, log.round_idx, parent,
                                 now if self._start is None else self._start)
                log._open.append(len(log.spans))
                log.spans.append(self._rec)
        return self

    def __exit__(self, exc_type: Optional[Type[BaseException]], *rest: Any) -> None:
        log, rec = self._log, self._rec
        if log is not None and rec is not None:
            rec.dur_s = time.perf_counter() - rec.start_s
            rec.nbytes += self.nbytes
            log._open.pop()
        self._ann.__exit__(exc_type, *rest)


def add(name: str, value: float) -> None:
    """Add ``value`` to counter ``name`` of the calling thread's log."""
    log = getattr(_bound, "log", None)
    if log is not None:
        log.counters[name] = log.counters.get(name, 0) + value
