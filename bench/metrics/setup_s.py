"""Process start to the window's start: imports, weights, data, warm-up
(compilation or cache loads) and the workers' start (host clock)."""


def read(run):
    return run.setup_s
