"""Device time of the dequant_fold Pallas kernel (its Mosaic custom call)
in the traced rounds, against the least time its bytes (int8 data,
weighted scales, accumulator read and write) take at the HBM peak;
percent.  The relayout copies around the call are not the kernel's.
Where the traced rounds folded updates and no such call ran, the name
has changed: that is an error, not a silent gap."""
from bench import costs, trace

KERNEL = r"^%dequant_fold[\w.]* = .*custom-call\("
QBLOCK = 8192


def read(run):
    if run.trace is None or not run.trace["ops"]:
        return None
    dev = sorted(run.trace["ops"])[0]
    ops = run.trace["ops"][dev]
    ns = trace.time_ns(ops, KERNEL, run.trace["window_ns"])
    calls = trace.count(ops, KERNEL, run.trace["window_ns"])
    if calls == 0 and any(r.record.fold_times_s for r in run.trace["rounds"]):
        raise RuntimeError(f"the traced rounds folded updates but no op matches {KERNEL}")
    padded = -(-run.n_params // QBLOCK) * QBLOCK
    f, b = costs.dequant_fold(padded, padded // QBLOCK)
    return trace.roofline_share(ns, f * calls, b * calls, run.peaks["bf16_flops_per_s"],
                                run.peaks["hbm_bytes_per_s"])
