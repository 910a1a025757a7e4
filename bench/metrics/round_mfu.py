"""Model FLOPs of the window's local training (3 x forward per training
sample and epoch) and evaluation (1 x forward per test sample), over the
window, the chips and the chip's bf16 peak, in percent.  Float32 matmuls
at JAX's default precision run as bf16 passes on the TPU."""


def read(run):
    fwd = run.module.forward_flops(run.cfg)
    s = run.cfg["silos"]
    per_round = fwd * (3 * sum(s["train"]) * run.cfg["local_epochs"] + sum(s["test"]))
    flops = per_round * len(run.rounds)
    return 100.0 * flops / (run.window_s * run.n_chips * run.peaks["bf16_flops_per_s"])
