"""Per round: the train phase less its slowest silo's training and less
the fold, i.e. the host path of s_msg_train and c_msg_train (serialize,
socket, deserialize) that the silos' compute does not hide; the mean
over rounds."""


def read(run):
    values = []
    for r in run.rounds:
        trains = [s.reported_s for s in run.silo_train_spans() if s.round_no == r.index]
        if trains:
            values.append(r.record.train_time_s - max(trains) - r.record.agg_time_s)
    return sum(values) / len(values) if values else None
