"""1 - (union of the intervals in which a device operation ran / traced
window), in percent, averaged over the chips (profiler trace)."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
