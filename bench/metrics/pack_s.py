"""Host seconds per round spent packing messages: counter ``pack_s``
(``tobytes`` and ``msgpack.packb`` in ``serialize_pytree`` and
``serialize_update``), summed over the driver and the silos.  Host
seconds of work, not wall time: the threads overlap."""
from bench import program_spans


def read(run):
    return program_spans.counter_per_round(run, "pack_s")
