"""Mean ClientResult.train_time_s per silo-round: one silo's local
training, per-step dispatch included, ending in block_until_ready."""


def read(run):
    spans = run.silo_train_spans()
    return sum(s.reported_s for s in spans) / len(spans) if spans else None
