"""Device time of the silos' compiled train steps (``jit_train_step``
programs) in the traced rounds, against the least time their HBM bytes
(the configuration's ``step_bytes``: weights read twice, new weights and
optimizer buffer written) or their FLOPs (3 x forward per sample of a
full batch) take at the chip's peaks; percent.  Where the traced rounds
trained and no program of that name ran, the name has changed: that is
an error, not a silent gap."""
from bench import trace

PROGRAMS = r"^jit_train_step$"


def read(run):
    if run.trace is None or not run.trace["modules"]:
        return None
    dev = sorted(run.trace["modules"])[0]
    modules, window = run.trace["modules"][dev], run.trace["window_ns"]
    ns = trace.time_ns(modules, PROGRAMS, window)
    calls = trace.count(modules, PROGRAMS, window)
    if calls == 0 and any(r.record.fold_times_s for r in run.trace["rounds"]):
        raise RuntimeError(f"the traced rounds trained but no program matches {PROGRAMS}")
    flops = 3.0 * run.module.forward_flops(run.cfg) * run.cfg["batch_size"]
    return trace.roofline_share(ns, flops * calls, run.module.step_bytes(run.cfg) * calls,
                                run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"])
