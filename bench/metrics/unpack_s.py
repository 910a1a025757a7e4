"""Host seconds per round spent unpacking messages: counter
``unpack_s`` (``msgpack.unpackb`` and ``np.frombuffer`` in
``deserialize_pytree`` and ``deserialize_update``), summed over the
driver and the silos.  Host seconds of work, not wall time: the threads
overlap."""
from bench import program_spans


def read(run):
    return program_spans.counter_per_round(run, "unpack_s")
