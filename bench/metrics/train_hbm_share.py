"""Device memory in use as a silo's local training ends: the mean of
counter ``hbm_in_use_bytes`` (the device's ``bytes_in_use`` after the
closing drain of ``FLClient.train``) per silo-round (its ``fl.train``
spans), over the chip's HBM; percent.  None where the program records
no such counter."""
from bench import program_spans


def read(run):
    recs = program_spans.records(run.rounds)
    if recs is None or not any("hbm_in_use_bytes" in rec.counters for rec in recs):
        return None
    silo_rounds = sum(1 for rec in recs for s in rec.spans if s.name == "fl.train")
    total = sum(rec.counters.get("hbm_in_use_bytes", 0.0) for rec in recs)
    return 100.0 * total / silo_rounds / run.peaks["hbm_bytes"] if silo_rounds else None
