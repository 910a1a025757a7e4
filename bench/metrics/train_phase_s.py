"""Mean RoundRecord.train_time_s: dispatch, the silos' training, the
replies and the fold (the round driver's own span)."""


def read(run):
    return sum(r.record.train_time_s for r in run.rounds) / len(run.rounds)
