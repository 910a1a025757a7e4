"""Host seconds per round spent copying device arrays to the host for
the wire: counter ``d2h_s`` (each device leaf's ``np.asarray`` in
``serialize_pytree``, the two flattened vectors in the int8 encode),
summed over the driver and the silos.  Host seconds of work, not wall
time: the threads overlap."""
from bench import program_spans


def read(run):
    return program_spans.counter_per_round(run, "d2h_s")
