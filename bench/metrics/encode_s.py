"""Host seconds per round in the silos' update encoders: the durations
of the ``fl.encode`` spans (``ClientCompressor.encode``: the flattened
weights' copy to the host, the delta, the quantizer, the error
feedback), summed over the silos.  Host seconds of work, not wall time:
the silos' threads overlap."""
from bench import program_spans


def read(run):
    return program_spans.span_s_per_round(run, ("fl.encode",))
