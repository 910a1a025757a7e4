"""Window wall time over the rounds completed in it (host clock)."""


def read(run):
    return run.window_s / len(run.rounds)
