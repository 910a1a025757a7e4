"""95th percentile, over every silo-round of the window, of the time from
the round's start (the s_msg_train dispatch) to that silo's c_msg_train
reaching the round driver (host clock, stamped at the transport's poll).
None where the window holds fewer than MIN_SAMPLES silo-rounds: a p95
needs some ten samples beyond it."""
import numpy as np

MIN_SAMPLES = 200


def read(run):
    samples = [t - r.start for r in run.rounds for t in r.receipts.values()]
    if len(samples) < MIN_SAMPLES:
        return None
    return float(np.percentile(samples, 95))
