"""Host seconds per silo-round inside the client's train-step calls:
counter ``step_dispatch_s`` (two clock reads around each call of the
compiled step in ``FLClient.train``) over the window's silo-rounds (its
``fl.train`` spans).  Each call returns once the step is queued, or
once the queue lets it in, so this is the host's dispatch cost, not the
device's step time."""
from bench import program_spans


def read(run):
    recs = program_spans.records(run.rounds)
    if recs is None:
        return None
    silo_rounds = sum(1 for rec in recs for s in rec.spans if s.name == "fl.train")
    total = sum(rec.counters.get("step_dispatch_s", 0.0) for rec in recs)
    return total / silo_rounds if silo_rounds else None
