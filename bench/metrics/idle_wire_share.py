"""Share of the traced window, in percent, in which device 0 ran no
operation while some thread (the driver or a silo) was inside a span
that moves message bytes: ``fl.serialize``, ``fl.send``, ``fl.recv``,
``fl.deserialize`` or ``fl.encode``.  The program's spans are put on the
trace's clock by the traced window's start."""
from bench import program_spans, trace


def read(run):
    if run.trace is None or not run.trace["ops"] or run.trace["window_s"] <= 0:
        return None
    wire = program_spans.intervals_ns(run, program_spans.WIRE)
    if wire is None:
        return None
    window = run.trace["window_ns"]
    idle = trace.gaps(run.trace["ops"][sorted(run.trace["ops"])[0]], window)
    busy_wire = trace.clip(trace.union(wire), window)
    return 100.0 * program_spans.overlap_ns(idle, busy_wire) / (window[1] - window[0])
