"""Host seconds per round inside the sockets: the durations of every
``fl.send`` span (one ``sendall`` of a frame) and ``fl.recv`` span (a
frame's reads, merged where they follow each other within 1 ms), summed
over the driver and the silos.  Host seconds of work, not wall time: a
frame's send and its receipt overlap."""
from bench import program_spans


def read(run):
    return program_spans.span_s_per_round(run, ("fl.send", "fl.recv"))
