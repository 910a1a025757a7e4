"""Reduction of a profiler trace to device numbers.

The JAX profiler writes an ``.xplane.pb`` that ``jax.profiler.ProfileData``
reads: a plane per device (``/device:TPU:0``) with an ``XLA Ops`` line
(one event per operation run) and an ``XLA Modules`` line (one event per
program run), and a host plane whose thread lines hold the benchmark's
``TraceAnnotation`` spans.  Device and host events share one clock, in
nanoseconds from the start of the trace.

Everything here works on plain ``(name, start_ns, duration_ns)`` events,
so that it can be checked on a synthetic trace.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]           # (name, start_ns, duration_ns)
Interval = Tuple[float, float]             # (start_ns, end_ns)

_HASH = re.compile(r"\(\d+\)$")


def load(trace_dir: str, n_devices: int) -> Dict[str, object]:
    """Device ops and modules of the first ``n_devices`` TPU planes, and
    the host's annotation spans, from the newest trace under the dir."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            if int(name.rsplit(":", 1)[1]) >= n_devices:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[name] = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules[name] = [(_HASH.sub("", e.name), e.start_ns, e.duration_ns)
                                     for e in line.events]
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns) for e in line.events
                            if e.name.startswith("bench_"))
    return {"ops": ops, "modules": modules, "host": sorted(host, key=lambda e: e[1])}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy_ns(events: Sequence[Event], window: Interval) -> float:
    """Length of the union of the events' intervals inside the window."""
    merged = clip(union((s, s + d) for _, s, d in events), window)
    return sum(e - s for s, e in merged)


def gaps(events: Sequence[Event], window: Interval) -> List[Interval]:
    """Idle intervals of the window: where no event runs."""
    merged = clip(union((s, s + d) for _, s, d in events), window)
    out, t = [], window[0]
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def time_ns(events: Sequence[Event], pattern: str, window: Interval) -> float:
    """Summed duration of the events whose name matches, inside the window."""
    rx = re.compile(pattern)
    return sum(e - s for name, s0, d in events if rx.search(name)
               for s, e in clip([(s0, s0 + d)], window))


def count(events: Sequence[Event], pattern: str, window: Interval) -> int:
    rx = re.compile(pattern)
    return sum(1 for name, s, d in events if rx.search(name) and s < window[1] and s + d > window[0])


def roofline_share(device_ns: float, flops: float, nbytes: float,
                   peak_flops: float, peak_bytes_s: float) -> Optional[float]:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the measured device time, in percent; None where
    nothing ran."""
    if device_ns <= 0:
        return None
    least_s = max(flops / peak_flops, nbytes / peak_bytes_s)
    return 100.0 * least_s / (device_ns * 1e-9)


def op_table(ops: Sequence[Event], modules: Sequence[Event], window: Interval,
             top: int = 10) -> List[List[object]]:
    """The device operations that took most time: ``[module/op, seconds]``,
    each op named by its HLO instruction within the program it ran in."""
    mods = sorted(modules, key=lambda e: e[1])
    totals: Dict[str, float] = {}
    j = 0
    for name, s, d in sorted(ops, key=lambda e: e[1]):
        if s + d <= window[0] or s >= window[1]:
            continue
        while j < len(mods) and mods[j][1] + mods[j][2] < s:
            j += 1
        module = mods[j][0] if j < len(mods) and mods[j][1] <= s else "?"
        instr = name.split(" = ", 1)[0].lstrip("%")
        key = f"{module}/{instr}"
        totals[key] = totals.get(key, 0.0) + d
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9] for k, v in ranked]


def gap_table(idle: Sequence[Interval], phases: Sequence[Tuple[str, float, float]],
              top: int = 10) -> List[List[object]]:
    """The longest idle gaps, each named by the round phase its middle
    falls in: ``[phase, seconds]``."""
    out = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        name = next((p for p, ps, pe in phases if ps <= mid < pe), "between rounds")
        out.append([name, (e - s) * 1e-9])
    return out
