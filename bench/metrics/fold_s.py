"""Mean RoundRecord.agg_time_s: the round's fold and finalize."""


def read(run):
    return sum(r.record.agg_time_s for r in run.rounds) / len(run.rounds)
