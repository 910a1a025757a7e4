"""Host seconds per round spent handing received bytes to the device:
counter ``h2d_s`` (each leaf's ``jnp.asarray`` in ``deserialize_pytree``,
the compressed payload's transfer in the fold), host side only, summed
over the driver and the silos.  Host seconds of work, not wall time: the
threads overlap."""
from bench import program_spans


def read(run):
    return program_spans.counter_per_round(run, "h2d_s")
