"""Mean RoundRecord.eval_time_s: s_msg_aggreg out, evaluation, c_msg_test
back (the round driver's own span)."""


def read(run):
    return sum(r.record.eval_time_s for r in run.rounds) / len(run.rounds)
