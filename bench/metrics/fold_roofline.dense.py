"""Device time of the dense fold programs (the streaming aggregator's
_scale_tree, _accum_tree and _scale_acc) in the traced rounds, against
the least time their bytes (read the updates and the accumulator, write
the accumulator) take at the HBM peak; percent.  Where the traced rounds
folded updates and no program of that name ran, the names have changed:
that is an error, not a silent gap."""
from bench import costs, trace

PROGRAMS = r"^jit__(scale_tree|accum_tree|scale_acc)_impl$"


def read(run):
    if run.trace is None or not run.trace["modules"]:
        return None
    dev = sorted(run.trace["modules"])[0]
    ns = trace.time_ns(run.trace["modules"][dev], PROGRAMS, run.trace["window_ns"])
    flops = nbytes = 0.0
    for r in run.trace["rounds"]:
        f, b = costs.dense_fold(run.n_params, len(r.record.fold_times_s))
        flops, nbytes = flops + f, nbytes + b
    if nbytes > 0 and ns <= 0:
        raise RuntimeError(f"the traced rounds folded updates but no program matches {PROGRAMS}")
    return trace.roofline_share(ns, flops, nbytes, run.peaks["bf16_flops_per_s"],
                                run.peaks["hbm_bytes_per_s"])
